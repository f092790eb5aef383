"""Mesh-sharded serving: kernel-vs-oracle cells at the same workload.

Runs the continuous-batching engine over one request stream through a
(matmul backend x executor) grid — the XLA oracle and the Pallas kernel
path (interpret mode on CPU), each on the default single-device executor
and on a ``("data", "model")`` mesh (``MeshExecutor``: weights TP over
"model", slab KV cache sharded per the decode recipe, Pallas kernels
shard_map-partitioned) — and reports per-step decode latency percentiles
(p50/p90/p99 pooled over decode+verify steps, gated by
``benchmarks/compare.py``), throughput, and the token-identity check
(greedy outputs MUST match across every cell; the acceptance bar is 0
mismatches).

On real accelerators the measurement runs in this process over
``jax.devices()`` (a process that has touched JAX holds the chips, so a
child could not open them).  Virtual CPU devices need ``XLA_FLAGS`` set
before jax initializes, so there the measurement runs in a WORKER
SUBPROCESS (``--worker``) and the parent (the CLI or ``benchmarks/run.py``)
parses the worker's JSON.

On virtual CPU devices the mesh numbers measure dispatch + emulated
collective overhead (and the kernel cells pay the Pallas interpreter), not
real scaling — the benchmark is a correctness + plumbing smoke there (CI),
and a scaling probe on real hardware.

    PYTHONPATH=src python benchmarks/sharded_serving.py [--tiny]
    PYTHONPATH=src python benchmarks/sharded_serving.py --mesh 2x4
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

if __package__ in (None, ""):  # ran as a script: make `benchmarks.` importable
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

_DEVICE_ENV = "--xla_force_host_platform_device_count"

#: (matmul_backend, use_mesh) grid; the first two cells keep the historic
#: BENCH_sharded layout (single then mesh on the resolved default backend)
#: so compare.py baselines stay meaningful across the kernel-cell addition.
_GRID = (("xla", False), ("xla", True),
         ("kernel_interpret", False), ("kernel_interpret", True))


def _measure(tiny: bool, mesh_shape, seed: int, backend: str,
             n_requests: int, rate: float) -> dict:
    """Worker-side measurement (jax already initialized with enough
    devices)."""
    import tempfile

    import numpy as np
    import jax
    from repro.configs.base import get_arch
    from repro.models import api
    from repro.serving import (Request, SchedulerConfig, ServeConfig,
                               ServingEngine, Telemetry, percentiles,
                               read_jsonl)

    base_cfg = get_arch("qwen2-1.5b").reduced().replace(
        num_layers=2 if tiny else 4, d_model=64 if tiny else 128,
        d_ff=128 if tiny else 256, vocab_size=256, head_dim=16,
        matmul_mode="bp_exact")
    rng = np.random.default_rng(seed)
    prompt_len = 8 if tiny else 16
    max_new_hi = 6 if tiny else 12
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (n_requests, prompt_len), 2,
        base_cfg.vocab_size), np.int32)
    max_news = rng.integers(2, max_new_hi + 1, size=n_requests).tolist()
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n_requests))
    sched = SchedulerConfig(lead_window=2)
    cache_T = prompt_len + max_new_hi + 4

    def reqs():
        return [Request(prompt=prompts[i], max_new_tokens=int(max_news[i]),
                        arrival_time=float(arrivals[i]))
                for i in range(n_requests)]

    def cell(matmul_backend, shape, tmp):
        cfg = base_cfg.replace(matmul_backend=matmul_backend)
        params = api.init(jax.random.PRNGKey(0), cfg)
        engine = ServingEngine(cfg, params, ServeConfig(
            max_new_tokens=max_new_hi, temperature=0.0,
            cache_backend=backend, block_size=4, mesh_shape=shape))
        engine.serve(reqs()[:2], n_slots=4, cache_T=cache_T,
                     sched_cfg=sched)                      # warmup compile
        metrics_path = os.path.join(
            tmp, f"{matmul_backend}_{'mesh' if shape else 'single'}.jsonl")
        tel = Telemetry(metrics_path=metrics_path)
        import dataclasses
        engine.serve_cfg = dataclasses.replace(engine.serve_cfg,
                                               telemetry=tel)
        try:
            rep = engine.serve(reqs(), n_slots=4, cache_T=cache_T,
                               sched_cfg=sched)
        finally:
            tel.close()
        step_ms = [1e3 * r["wall_s"] for r in read_jsonl(metrics_path)
                   if r.get("kind") in ("decode", "verify")]
        toks = [list(r.tokens) for r in
                sorted(rep.results, key=lambda r: r.request_id)]
        return {
            "matmul_backend": matmul_backend,
            "mesh_shape": list(shape) if shape else None,
            "decode_steps": int(rep.steps),
            "decode_s": float(rep.decode_s),
            # gated: suffix-matched by benchmarks/compare.py
            "per_step_ms": percentiles(step_ms),
            "mean_step_ms": float(1e3 * rep.decode_s / max(rep.steps, 1)),
            "prefill_s": float(rep.prefill_s),
            "decode_tokens_per_s": float(rep.decode_tokens_per_s),
            "slot_utilization": float(rep.slot_utilization),
        }, toks

    cells, all_toks = [], []
    with tempfile.TemporaryDirectory(prefix="sharded_serving_") as tmp:
        for matmul_backend, use_mesh in _GRID:
            c, toks = cell(matmul_backend,
                           tuple(mesh_shape) if use_mesh else None, tmp)
            cells.append(c)
            all_toks.append(toks)
    mismatches = sum(sum(a != b for a, b in zip(all_toks[0], toks))
                     for toks in all_toks[1:])

    def mean_ms(matmul_backend, use_mesh):
        i = _GRID.index((matmul_backend, use_mesh))
        return cells[i]["mean_step_ms"]

    return {
        "backend": backend,
        "n_requests": n_requests,
        "n_devices": len(jax.devices()),
        "cells": cells,
        "single_per_step_ms": mean_ms("xla", False),
        "sharded_per_step_ms": mean_ms("xla", True),
        "sharded_vs_single_step_ratio": (
            mean_ms("xla", True) / max(mean_ms("xla", False), 1e-9)),
        "kernel_vs_oracle_mesh_ratio": (
            mean_ms("kernel_interpret", True)
            / max(mean_ms("xla", True), 1e-9)),
        "token_mismatches": int(mismatches),
    }


def run(tiny: bool = False, mesh_shape=(2, 4), seed: int = 0,
        backend: str = "slab", n_requests: int = None,
        rate: float = 0.5) -> dict:
    """Measure in-process on real accelerators; on the CPU spawn the
    worker with enough virtual devices and parse its JSON.  (Callable from
    ``benchmarks/run.py``, whose jax is already initialized — device count
    is locked at first backend init.)"""
    from benchmarks.common import holds_accelerator
    if n_requests is None:
        n_requests = 6 if tiny else 16
    if holds_accelerator():
        return _measure(tiny, mesh_shape, seed, backend, n_requests, rate)
    n_dev = int(mesh_shape[0]) * int(mesh_shape[1])
    env = dict(os.environ)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith(_DEVICE_ENV)]
    env["XLA_FLAGS"] = " ".join(flags + [f"{_DEVICE_ENV}={n_dev}"])
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    cmd = [sys.executable, os.path.abspath(__file__), "--worker",
           "--mesh", f"{mesh_shape[0]}x{mesh_shape[1]}",
           "--seed", str(seed), "--backend", backend,
           "--requests", str(n_requests), "--rate", str(rate)]
    if tiny:
        cmd.append("--tiny")
    out = subprocess.run(cmd, capture_output=True, text=True, timeout=900,
                         env=env)
    if out.returncode != 0:
        raise RuntimeError(
            f"sharded serving worker failed:\n{out.stdout}\n{out.stderr}")
    return json.loads(out.stdout.splitlines()[-1])


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke size (seconds, not minutes)")
    ap.add_argument("--mesh", default="2x4",
                    help="mesh shape DATAxMODEL (e.g. 2x4)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", default="slab", choices=["slab", "paged"])
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--rate", type=float, default=0.5)
    ap.add_argument("--worker", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    mesh_shape = tuple(int(d) for d in args.mesh.lower().split("x"))

    if args.worker:
        r = _measure(args.tiny, mesh_shape, args.seed, args.backend,
                     args.requests or (6 if args.tiny else 16), args.rate)
        print(json.dumps(r))
        return 0

    r = run(tiny=args.tiny, mesh_shape=mesh_shape, seed=args.seed,
            backend=args.backend, n_requests=args.requests, rate=args.rate)
    from benchmarks.common import save_artifact
    path = save_artifact("BENCH_sharded", r)
    print(f"backend={r['backend']} requests={r['n_requests']} "
          f"devices={r['n_devices']}")
    for c in r["cells"]:
        where = (f"mesh {tuple(c['mesh_shape'])}" if c["mesh_shape"]
                 else "single")
        p = c["per_step_ms"] or {}
        print(f"{c['matmul_backend']:>16s} / {where:<10s} "
              f"{c['decode_steps']:3d} steps, per-step ms "
              f"p50={p.get('p50', float('nan')):.2f} "
              f"p90={p.get('p90', float('nan')):.2f} "
              f"p99={p.get('p99', float('nan')):.2f}  "
              f"{c['decode_tokens_per_s']:.1f} tok/s")
    print(f"sharded/single per-step ratio (xla): "
          f"{r['sharded_vs_single_step_ratio']:.2f}x; "
          f"kernel/oracle on the mesh: "
          f"{r['kernel_vs_oracle_mesh_ratio']:.2f}x "
          f"(virtual-CPU meshes emulate collectives and the kernel cells "
          f"pay the Pallas interpreter — correctness smoke, not a scaling "
          f"claim)")
    print(f"token mismatches: {r['token_mismatches']}")
    print(f"artifact: {path}")
    if r["token_mismatches"]:
        print("ERROR: outputs diverged across backend/mesh cells",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
