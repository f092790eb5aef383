"""One run of one cell: build, warm up, measure a window, check, report.

The order inside one process (``run.py`` calls :func:`main`):

1. ``jax.devices()[0]`` must be a TPU with as many chips as the cell
   asks for, else the run exits non-zero before any model is built.
2. Weights are made from ``--seed`` (``weights.py``) on the devices that
   serve them: on a cell's ``serve.mesh_shape``, in the shardings the
   program places them with.
3. The real ``FrontDoor`` starts over one ``Replica``: the paged
   ``ServeLoop``, ``matmul_backend="auto"``, kernels on, on the cell's
   mesh where it names one.
4. Every prefill shape the mix can reach (power-of-two bucket x group
   size) and the decode step are compiled, from the persistent cache
   after the first run; then one request crosses the HTTP path.
5. The open-loop client (``loadgen.py``, a child that never imports JAX)
   sends the mix for ``--seconds``; with ``--trace 1`` the profiler
   records a sub-window in the middle.
6. After the window: peak memory is read, the program is stopped and
   freed, and a seeded sample of the finished requests is compared with
   the configuration's float32 reference (``reference.py``, or the
   module its ``reference_file`` names).
7. The last stdout line is the result object; the numbers compared, with
   their limits, are the last stderr lines and the result's last key.
"""

from __future__ import annotations

import gc
import json
import math
import os
import shutil
import subprocess
import sys
import tempfile
import time

from bench import spec as spec_mod
from bench import stats, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
EXIT_NO_CHIP = 3
WARM_MAX_NEW = 2
DRAIN_S = 120.0         # how long the client waits past the window


class NoAccelerator(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def require_accelerator(chips: int):
    """The devices of a TPU with at least ``chips`` chips, else raise."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"first device is {devices[0].platform!r}, "
                            f"not a TPU")
    if len(devices) < chips:
        raise NoAccelerator(f"cell needs {chips} chips, found "
                            f"{len(devices)}")
    return devices


def lookup_peaks(device_kind: str) -> dict:
    from bench import peaks
    return peaks.lookup(device_kind)


def enable_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where set, else the fixed ``<checkout>/.jax_cache``.  Every program is
    cached, however short its compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class CompileCounter:
    """Counts the process's compilations (persistent-cache hits included:
    each is a program the process did not hold yet), with their times."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.times = []
        event = dispatch.BACKEND_COMPILE_EVENT

        def listen(name, _secs, **_kw):
            if name == event:
                self.times.append(time.monotonic())

        jax.monitoring.register_event_duration_secs_listener(listen)

    def between(self, a: float, b: float) -> int:
        return sum(1 for t in self.times if a <= t <= b)


def arch_config(conf: dict, name: str):
    """The program's ``ArchConfig`` for a configuration file: the sizes
    from its published keys, and every key of its ``serving`` block that
    is an ``ArchConfig`` field.  ``name`` and ``matmul_backend`` are the
    harness's, and a size is stated once, under its published key."""
    import dataclasses
    from repro.configs.base import ArchConfig
    H = conf["num_attention_heads"]
    kw = dict(
        num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        num_heads=H, num_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        head_dim=conf.get("head_dim") or conf["hidden_size"] // H,
        rope_theta=float(conf["rope_theta"]),
        tie_embeddings=conf["tie_word_embeddings"],
        norm_eps=float(conf["rms_norm_eps"]))
    fields = {f.name for f in dataclasses.fields(ArchConfig)}
    for k, v in conf["serving"].items():
        if k not in fields:
            continue
        if k in kw or k in ("name", "matmul_backend"):
            raise ValueError(f"serving.{k} is not the configuration's to "
                             f"set: the harness sets it")
        kw[k] = tuple(v) if isinstance(v, list) else v
    return ArchConfig(name=name, matmul_backend="auto", **kw)


def mesh_shape(sp: dict):
    """The cell's ``serve.mesh_shape`` as a ``(data, model)`` tuple, None
    where it names none; refused unless its devices are the cell's
    ``chips``."""
    shape = sp["cell"]["serve"].get("mesh_shape")
    if shape is None:
        return None
    shape = tuple(int(n) for n in shape)
    chips = int(sp["entry"]["chips"])
    if len(shape) != 2 or min(shape) < 1 or math.prod(shape) != chips:
        raise ValueError(f"cell {sp['entry']['name']!r}: mesh_shape "
                         f"{list(shape)} is not (data, model) over its "
                         f"{chips} chips")
    return shape


def cell_mesh(shape, devices):
    """``(mesh, the cell's devices)``: the program's serving mesh for a
    ``mesh_shape`` (None for none) and the devices it spans, else the
    first device alone."""
    if shape is None:
        return None, list(devices[:1])
    from repro.serving.executor import make_serving_mesh
    mesh = make_serving_mesh(shape)
    return mesh, list(mesh.devices.flat)


def served_model(root: str, sp: dict, mesh) -> dict:
    """What the weights are drawn from: the configuration's reference
    module, the served layout (the module's ``layout`` where it has one)
    and the shardings the program places that layout with on ``mesh``
    (``MeshExecutor._place_params``; None on one device)."""
    from bench import weights
    ref = spec_mod.reference(root, sp["conf"])
    shapes = getattr(ref, "layout", weights.layout)(sp["conf"])
    shardings = None
    if mesh is not None:
        from repro.distributed.sharding import named_shardings
        shardings = named_shardings(shapes, "decode", mesh)
    return {"ref": ref, "shapes": shapes, "shardings": shardings}


def draw_weights(sp: dict, model: dict, seed: int):
    """The served parameters from ``seed``, drawn into the model's
    shardings by ``weights.build``."""
    from bench import weights
    return weights.build(sp["conf"], seed, model["shardings"],
                         shapes=model["shapes"],
                         draw=getattr(model["ref"], "draw_leaf", None))


def check_layout(have, cfg) -> None:
    """The benchmark's weight layout ``have`` must be the tree the
    program serves."""
    import jax
    from repro.models import api
    from repro.models.layers import quantize_dense_params
    want = quantize_dense_params(jax.eval_shape(
        lambda k: api.init(k, cfg), jax.random.PRNGKey(0)))
    sig = lambda t: jax.tree.map(lambda s: (s.shape, str(s.dtype)), t)
    if sig(want) != sig(have):
        raise ValueError("the benchmark's weight layout differs from the "
                         "program's served parameter tree")


def peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def prefill_buckets(pmin: int, pmax: int, cache_T: int):
    from repro.serving.scheduler import prefill_bucket_len
    return sorted({prefill_bucket_len(n, cache_T)
                   for n in range(pmin, pmax + 1)})


class Server:
    """The system under test: engine, replica and front door of a cell."""

    def __init__(self, sp: dict, params, *, trace: bool):
        from repro.serving import (FrontDoor, Replica, ServeConfig,
                                   ServingEngine)
        from repro.serving.scheduler import SchedulerConfig
        from repro.serving.telemetry import Telemetry
        self.sp = sp
        sv = sp["cell"]["serve"]
        lim = traffic.max_lengths(sp["mix"])
        self.cfg = arch_config(sp["conf"], sp["entry"]["config"])
        sc = ServeConfig(max_new_tokens=lim["output"], temperature=0.0,
                         eos_id=None, cache_backend="paged",
                         block_size=sv["block_size"],
                         mesh_shape=mesh_shape(sp),
                         telemetry=(Telemetry(annotate_device=True)
                                    if trace else None))
        self.engine = ServingEngine(self.cfg, params, sc)
        cache_T = lim["prompt"] + lim["output"] + sc.cache_margin
        self.replica = Replica(
            self.engine, name="r0", n_slots=sv["n_slots"], cache_T=cache_T,
            num_blocks=sv.get("num_blocks"),
            sched_cfg=SchedulerConfig(
                max_prefill_batch=sv["max_prefill_batch"],
                lead_window=sv["lead_window"],
                max_waiting=sv["max_waiting"]))
        self.loop = self.replica.loop
        self.door = FrontDoor([self.replica])
        self.started = False

    def warm(self, seed: int) -> int:
        """Compile every shape the mix can reach, on the loop itself,
        before its worker starts: for each prefill bucket and each group
        size, that many prompts of the bucket's length admitted together,
        each decoding one token.  Returns the number of requests run."""
        import numpy as np
        from repro.serving import Request
        loop = self.loop
        mix = self.sp["mix"]
        vocab = self.sp["conf"]["vocab_size"]
        rng = np.random.default_rng(int(seed) + 1)
        n = 0
        gmax = self.sp["cell"]["serve"]["max_prefill_batch"]
        for b in prefill_buckets(int(mix["prompt_len"]["min"]),
                                 int(mix["prompt_len"]["max"]),
                                 loop.cm.cache_T):
            for g in range(1, gmax + 1):
                for _ in range(g):
                    loop.submit(Request(
                        prompt=rng.integers(2, vocab, b).astype(np.int32),
                        max_new_tokens=WARM_MAX_NEW))
                    n += 1
                while True:
                    loop._drain_inbox()
                    if not (loop.arrivals or len(loop.rq) or loop.active):
                        break
                    loop.sweep()
                    loop._step()
        return n

    def start(self) -> None:
        from repro.serving import FrontDoorClient
        self.door.start()
        self.started = True
        client = FrontDoorClient("127.0.0.1", self.door.port, timeout_s=600)
        out = client.generate([5, 6, 7, 8], max_new_tokens=WARM_MAX_NEW,
                              stream=True)
        if out["finish_reason"] != "length":
            raise RuntimeError(f"warm-up request through the front door "
                               f"finished {out['finish_reason']!r}")

    def window(self, schedule, window_s: float, *, trace_s: float = 0.0,
               profile_dir=None):
        """Run the open-loop client over ``schedule``; returns
        ``(client records, t0, profile info)``.  All times are on
        ``time.monotonic()``, which is also ``perf_counter`` here."""
        job = {"host": "127.0.0.1", "port": self.door.port,
               "window_s": window_s, "hard_stop_s": window_s + DRAIN_S,
               "requests": schedule}
        fd, path = tempfile.mkstemp(prefix="bench_job_", suffix=".json")
        try:
            with os.fdopen(fd, "w", encoding="utf-8") as f:
                json.dump(job, f)
            child = subprocess.Popen(
                [sys.executable, os.path.join(HERE, "loadgen.py"), path],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
            try:
                if child.stdout.readline().strip() != "ready":
                    raise RuntimeError("load generator did not start")
                t0 = time.monotonic() + 0.1
                child.stdin.write(f"{t0!r}\n")
                child.stdin.flush()
                prof = None
                if trace_s > 0:
                    prof = self._profile(t0, window_s, trace_s, profile_dir)
                out, _ = child.communicate(timeout=window_s + DRAIN_S + 60)
            finally:
                if child.poll() is None:
                    child.kill()
                    child.wait()
        finally:
            os.unlink(path)
        if child.returncode != 0:
            raise RuntimeError(f"load generator exited {child.returncode}")
        return json.loads(out)["requests"], t0, prof

    @staticmethod
    def _profile(t0, window_s, trace_s, profile_dir):
        import jax
        a = t0 + max(0.0, (window_s - trace_s) / 2)
        time.sleep(max(0.0, a - time.monotonic()))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(profile_dir, profiler_options=opts)
        try:
            mark = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench_trace_start"):
                pass
            time.sleep(trace_s)
            with jax.profiler.TraceAnnotation("bench_trace_end"):
                pass
        finally:
            jax.profiler.stop_trace()
        return {"mark_perf_s": mark}

    def step_records(self, a: float, b: float):
        """The loop's prefill/decode records that lie wholly in [a, b]
        (host clock), each with its interval as ``t0``/``t1``."""
        base = self.loop._wall0
        out = []
        for rec in list(self.loop.stream):
            if rec["kind"] not in ("prefill", "decode"):
                continue
            t1 = base + rec["ts_s"]
            t0 = t1 - rec["wall_s"]
            if a <= t0 and t1 <= b:
                out.append(dict(rec, t0=t0, t1=t1))
        return out

    def stop(self) -> None:
        if self.started:
            self.door.stop()
            self.started = False

    def free(self) -> None:
        """Release the program's device memory (weights and KV pages)."""
        import jax
        self.stop()
        for leaf in (jax.tree.leaves(self.engine.executor.params)
                     + jax.tree.leaves(self.loop.cm.pages)):
            leaf.delete()
        self.engine = self.replica = self.loop = self.door = None
        gc.collect()


def check_outputs(sp: dict, model: dict, schedule, records, seed: int,
                  window_s: float, *, control_bits: int = 0):
    """Compare a seeded sample of the window's finished requests, the
    longest among them, with the float32 reference, on weights drawn as
    the served ones were.  Returns the widest gap (and the control's, with
    ``control_bits``)."""
    import numpy as np
    reference = model["ref"]
    conf, mix = sp["conf"], sp["mix"]
    done = [r for r in records if stats.in_window(r, window_s) and r["ok"]]
    if not done:
        return None, None, 0
    done.sort(key=lambda r: (len(r["tokens"]), r["id"]))
    longest = done[-1]
    rng = np.random.default_rng(int(seed) + 2)
    k = min(int(sp["cell"]["check"]["n_requests"]) - 1, len(done) - 1)
    picks = [longest] + [done[i] for i in rng.choice(len(done) - 1, k,
                                                      replace=False)]
    lim = traffic.max_lengths(mix)
    S = reference.seq_len(lim["prompt"], lim["output"])
    params = draw_weights(sp, model, seed)
    prompts = {r["id"]: r["prompt"] for r in schedule}
    widest, widest_ctrl = 0.0, 0.0
    for r in picks:
        gaps, ctrl = reference.served_gaps(
            params, conf, prompts[r["id"]], r["tokens"], S=S, n_out=lim["output"],
            control_bits=control_bits)
        widest = max(widest, max(gaps))
        if ctrl:
            widest_ctrl = max(widest_ctrl, max(ctrl))
    import jax
    for leaf in jax.tree.leaves(params):
        leaf.delete()
    return widest, (widest_ctrl if control_bits else None), \
        sum(len(r["tokens"]) for r in picks)


def finite(x):
    return x if x is not None and math.isfinite(x) else None


def run_cell(root: str, name: str, seed: int, seconds: float, trace: bool,
             t_start: float, *, control_bits: int = 0) -> dict:
    """Everything of one run but the printing; returns the result fields
    and the readings the earlier lines report."""
    marks = [("start", t_start)]
    sp = spec_mod.cell(root, name)
    chips = int(sp["entry"]["chips"])
    shape = mesh_shape(sp)
    enable_compile_cache(root)
    devices = require_accelerator(chips)
    import jax
    dev = devices[0]
    pk = lookup_peaks(dev.device_kind)
    counter = CompileCounter()
    mesh, cell_devices = cell_mesh(shape, devices)
    marks.append(("jax_and_devices", time.monotonic()))
    cfg = arch_config(sp["conf"], sp["entry"]["config"])
    model = served_model(root, sp, mesh)
    check_layout(model["shapes"], cfg)
    marks.append(("layout_check", time.monotonic()))
    params = draw_weights(sp, model, seed)
    jax.block_until_ready(params)
    marks.append(("weights", time.monotonic()))
    server = Server(sp, params, trace=trace)
    del params
    marks.append(("engine", time.monotonic()))
    n_warm = server.warm(seed)
    marks.append(("warm_up", time.monotonic()))
    server.start()
    marks.append(("front_door", time.monotonic()))
    n_programs = counter.between(0.0, time.monotonic())
    log(f"warm-up: {n_warm} requests, {n_programs} programs compiled or "
        f"loaded, {marks[-2][1] - marks[-3][1]:.1f} s")

    rate = float(sp["cell"]["rate_rps"])
    schedule = traffic.schedule(sp["mix"], rate, seconds, seed,
                                sp["conf"]["vocab_size"])
    trace_s = min(float(sp["cell"]["trace_s"]), seconds) if trace else 0.0
    prof_dir = tempfile.mkdtemp(prefix="bench_profile_") if trace else None
    records, t0, prof = server.window(schedule, seconds, trace_s=trace_s,
                                      profile_dir=prof_dir)
    t_end = time.monotonic()
    setup_s = t0 - t_start
    marks.append(("load_generator", t0))
    log("setup_phases_s " + " ".join(
        f"{k}={b - a:.3f}" for (_, a), (k, b) in zip(marks, marks[1:])))
    log(f"memory_bytes_limit {(dev.memory_stats() or {}).get('bytes_limit')}")
    n_compiles = counter.between(t0, t_end)
    peak = peak_bytes(cell_devices)
    log(f"memory_peak_bytes_per_device "
        f"{[peak_bytes([d]) for d in cell_devices]}")
    steps = server.step_records(t0, t0 + seconds)
    server.stop()
    ids = {r["request_id"] for r in records
           if stats.in_window(r, seconds) and r["request_id"] is not None}
    req_recs = [r for r in server.loop.stream
                if r["kind"] == "request" and r["request_id"] in ids]
    e2e = stats.client_metrics(records, seconds)
    late = stats.lateness_p99_ms(records)
    log(f"window: {e2e['n_due']} requests due, {e2e['n_failed']} failed, "
        f"{e2e['n_gaps']} token gaps, {len(steps)} step records")
    log(f"compiles_in_window {n_compiles}")
    log(f"generator_lateness_p99_ms {late}")
    log(f"backlog_trend {stats.backlog_trend(records, seconds)}")
    log(f"decode_step_ms {stats.decode_step_ms(steps)}")
    log(f"prefill_groups {stats.prefill_groups(steps)}")
    log(f"ttft_ms {stats.ttft_profile(records, seconds)}")

    out = {"setup_s": setup_s, "e2e": e2e, "peak": peak, "device": dev,
           "n_devices": len(jax.devices()), "n_compiles": n_compiles,
           "breakdown": None, "layer": {}, "busy_s": None, "window_s": None}
    if trace:
        from bench import trace_reduce
        try:
            tr = trace_reduce.load(prof_dir)
        finally:
            shutil.rmtree(prof_dir, ignore_errors=True)
        offset = tr["marks"]["bench_trace_start"] - prof["mark_perf_s"] * 1e9
        ctx = {"conf": sp["conf"], "cell": sp["cell"], "peaks": pk,
               "steps": steps, "requests": req_recs, "client": e2e,
               "trace": tr, "chips": len(cell_devices),
               "clock": lambda s: s * 1e9 + offset, "window_s": seconds}
        for mname, read in spec_mod.metric_readers(root).items():
            v = read(ctx)
            if v is not None:
                out["layer"][mname] = v
        a, b = trace_reduce.window(tr)
        out["busy_s"] = trace_reduce.busy_ns(tr) * 1e-9
        out["window_s"] = (b - a) * 1e-9
        out["breakdown"] = {"device_ops": trace_reduce.top_ops(tr),
                            "idle_gaps": trace_reduce.idle_gaps(tr)}
    server.free()
    t_ref = time.monotonic()
    widest, ctrl, n_tok = check_outputs(sp, model, schedule, records, seed,
                                        seconds, control_bits=control_bits)
    log(f"reference: {n_tok} served tokens compared in "
        f"{time.monotonic() - t_ref:.1f} s")
    out["widest_gap"], out["control_gap"] = widest, ctrl
    return out, sp


def main(argv, root: str, t_start: float) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        out, sp = run_cell(root, args.workload, args.seed, args.seconds,
                           bool(args.trace), t_start)
    except NoAccelerator as e:
        log(f"bench: no accelerator for this cell: {e}")
        return EXIT_NO_CHIP
    e2e = out["e2e"]
    limit = float(sp["cell"]["check"]["widest_logit_gap_limit"])
    widest = out["widest_gap"]
    checks = {"failed_requests": {"value": e2e["n_failed"], "limit": 0},
              "widest_logit_gap": {"value": widest, "limit": limit}}
    correct = (e2e["n_failed"] == 0 and widest is not None
               and widest <= limit)
    names = [m["name"] for m in spec_mod.cell_metrics(
        sp["bench"], bool(args.trace), args.workload)]
    units = {m["name"]: m["unit"] for m in
             sp["bench"]["end_to_end"] + sp["bench"]["per_layer"]}
    values = dict(e2e, setup_s=out["setup_s"], **out["layer"])
    metrics = {n: {"value": finite(values.get(n)), "unit": units[n]}
               for n in names if n in values}
    dev = out["device"]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": out["n_devices"], "memory_peak_bytes": out["peak"]}
    result = {"correct": bool(correct), "attempted": e2e["n_due"],
              "failed": e2e["n_failed"], "metrics": metrics,
              "device": device}
    if args.trace:
        device["busy_s"] = out["busy_s"]
        device["window_s"] = out["window_s"]
        result["breakdown"] = out["breakdown"]
    result["checks"] = checks
    for k, c in checks.items():
        log(f"check {k} {c['value']} limit {c['limit']}")
    print(json.dumps(result), flush=True)
    return 0
