"""Production-mix serving benchmark: the full observability stack under a
realistic multi-tenant load, gated on step-time percentiles.

Three tenants share per-tenant system prompts (exercising the paged
backend's prefix-sharing trie), user suffixes mix short / medium / long
prompts (exercising ragged grouped prefill), arrivals are Poisson, and the
serve runs with prompt-lookup speculation, full telemetry (metrics JSONL +
Chrome trace), and the hardware-cost ``SparsityProbe`` enabled — i.e. the
production configuration, not the stripped-down fast path.

The artifact (``BENCH_production_mix.json``) carries ``per_step_ms``
{p50, p90, p99} pooled over decode+verify steps and ``tokens_per_s`` —
both gated by ``benchmarks/compare.py`` — plus the run's measured-traffic
hardware estimate (mean bit sparsity, modeled cycles/MAC per method,
array utilization, Table III energy).

The same mix also runs as a **mesh (tensor-parallel) leg**: the identical
workload on a ``("data", "model")`` ``MeshExecutor``.  On real accelerators
it runs in this process over ``jax.devices()``; on the CPU it runs in a
worker subprocess with virtual devices (``XLA_FLAGS`` must be set before
jax initializes — the ``benchmarks/sharded_serving.py`` harness pattern).
It is saved as its own gated artifact (``BENCH_production_mix_mesh.json``) with
its own telemetry files (``production_mix_mesh_*``).

    PYTHONPATH=src python benchmarks/production_mix.py [--tiny]
    PYTHONPATH=src python benchmarks/production_mix.py --telemetry DIR
    PYTHONPATH=src python benchmarks/production_mix.py --mesh 2x4

``--telemetry DIR`` keeps the runs' metrics JSONL + trace + sparsity
profiles under DIR (CI uploads them as artifacts); without it they land in
a temp dir used only to compute the percentiles.  ``--mesh none`` skips
the mesh leg.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile

import numpy as np

if __package__ in (None, ""):  # ran as a script: make `benchmarks.` importable
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

_DEVICE_ENV = "--xla_force_host_platform_device_count"


def _poisson_arrivals(rng, n: int, rate: float) -> np.ndarray:
    """Arrival times (decode-step clock) of a Poisson process with ``rate``
    requests per decode step."""
    gaps = rng.exponential(1.0 / rate, size=n)
    return np.cumsum(gaps)


def run(tiny: bool = False, seed: int = 0, probe_every: int = 2,
        n_slots: int = None, n_requests: int = None, rate: float = 0.7,
        block_size: int = 8, telemetry_dir: str = None, mesh_shape=None,
        matmul_backend: str = None):
    import dataclasses

    import jax
    from repro.configs.base import get_arch
    from repro.models import api
    from repro.serving import (Request, SchedulerConfig, ServeConfig,
                               ServingEngine, SparsityProbe, Telemetry,
                               percentiles, read_jsonl, reduce_stream)

    if n_slots is None:
        n_slots = 3 if tiny else 6
    if n_requests is None:
        n_requests = 6 if tiny else 24
    n_tenants = 3
    sys_len = 8 if tiny else 16          # shared per-tenant system prompt
    # mixed prompt lengths: user suffixes drawn from three tiers
    tiers = (2, 4, 6) if tiny else (4, 12, 24)
    max_new_hi = 6 if tiny else 16
    margin = 4

    cfg = get_arch("qwen2-1.5b").reduced().replace(
        num_layers=2 if tiny else 4, d_model=64 if tiny else 128,
        d_ff=128 if tiny else 256, vocab_size=256, head_dim=16,
        matmul_mode="bp_exact")   # int8 dual factors: what the probe taps
    if matmul_backend is not None:
        cfg = cfg.replace(matmul_backend=matmul_backend)
    params = api.init(jax.random.PRNGKey(0), cfg)

    rng = np.random.default_rng(seed)
    sys_prompts = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (n_tenants, sys_len), 2,
                           cfg.vocab_size), np.int32)
    # a short per-tenant phrase repeated inside every suffix gives the
    # prompt-lookup n-gram drafter something to actually match
    phrases = np.asarray(
        jax.random.randint(jax.random.PRNGKey(2), (n_tenants, 3), 2,
                           cfg.vocab_size), np.int32)
    tenants = rng.integers(0, n_tenants, size=n_requests)
    suffix_lens = rng.choice(tiers, size=n_requests)
    prompts = []
    for i in range(n_requests):
        t = int(tenants[i])
        uniq = rng.integers(2, cfg.vocab_size, size=int(suffix_lens[i]))
        prompts.append(np.concatenate(
            [sys_prompts[t], phrases[t], uniq.astype(np.int32),
             phrases[t]]).astype(np.int32))
    max_news = rng.integers(2, max_new_hi + 1, size=n_requests).tolist()
    arrivals = _poisson_arrivals(rng, n_requests, rate)

    max_prompt = max(len(p) for p in prompts)
    cache_T = max_prompt + max_new_hi + margin
    # generous pool: this benchmark measures the instrumented steady state,
    # not preemption churn (paged_memory covers pool pressure)
    num_blocks = 1 + (n_slots + 2) * cache_T // block_size

    def reqs():
        return [Request(prompt=prompts[i], max_new_tokens=int(max_news[i]),
                        arrival_time=float(arrivals[i]))
                for i in range(n_requests)]

    sched = SchedulerConfig(lead_window=3)
    probe = SparsityProbe(probe_every=probe_every)
    engine = ServingEngine(cfg, params, ServeConfig(
        max_new_tokens=max_new_hi, temperature=0.0,
        cache_backend="paged", block_size=block_size,
        draft="prompt_lookup", num_draft_tokens=3, probe=probe,
        mesh_shape=tuple(mesh_shape) if mesh_shape else None))

    # warmup with the probe already attached: compiles the probed step-fn
    # variants AND builds the host-side Monte-Carlo interpolation tables,
    # so the timed run measures the instrumented steady state
    engine.serve(reqs()[:2], n_slots=n_slots, cache_T=cache_T,
                 num_blocks=num_blocks, sched_cfg=sched)

    own_tmp = None
    if telemetry_dir is None:
        own_tmp = tempfile.TemporaryDirectory(prefix="production_mix_")
        telemetry_dir = own_tmp.name
        keep_paths = False
    else:
        keep_paths = True
    stem = "production_mix_mesh" if mesh_shape else "production_mix"
    metrics_path = os.path.join(telemetry_dir, f"{stem}_metrics.jsonl")
    trace_path = os.path.join(telemetry_dir, f"{stem}_trace.json")
    profile_path = os.path.join(
        telemetry_dir, "sparsity_profile_mesh.json" if mesh_shape
        else "sparsity_profile.json")

    tel = Telemetry(metrics_path=metrics_path, trace_path=trace_path)
    saved_cfg = engine.serve_cfg
    engine.serve_cfg = dataclasses.replace(saved_cfg, telemetry=tel)
    try:
        report = engine.serve(reqs(), n_slots=n_slots, cache_T=cache_T,
                              num_blocks=num_blocks, sched_cfg=sched)
    finally:
        engine.serve_cfg = saved_cfg
        tel.close()

    records = read_jsonl(metrics_path)
    step_ms = [1e3 * r["wall_s"] for r in records
               if r.get("kind") in ("decode", "verify")]
    prefill_ms = [1e3 * r["wall_s"] for r in records
                  if r.get("kind") == "prefill"]
    summary = reduce_stream(records)

    # greedy identity vs the plain fast path: no speculation, no probe, no
    # telemetry — the production mix must not change tokens.  Single-device
    # the reference is the slab backend (slab vs paged is a pure storage
    # transform there, so this also gates cross-backend identity); on the
    # mesh leg the reference rides the same mesh AND the paged backend,
    # because a mesh reorders float reductions differently per executor and
    # cache layout (split-KV slab vs replicated pages), so near-tie
    # argmaxes on a random-init toy model may legitimately differ across
    # those — cross-executor identity is a separate invariant, covered by
    # tests/test_sharded_serving.py and tests/test_mesh_kernels.py on
    # their pinned workloads.
    if mesh_shape:
        plain = ServingEngine(cfg, params, ServeConfig(
            max_new_tokens=max_new_hi, temperature=0.0,
            cache_backend="paged", block_size=block_size,
            mesh_shape=tuple(mesh_shape)))
        base = plain.serve(reqs(), n_slots=n_slots, cache_T=cache_T,
                           num_blocks=num_blocks, sched_cfg=sched)
    else:
        plain = ServingEngine(cfg, params, ServeConfig(
            max_new_tokens=max_new_hi, temperature=0.0))
        base = plain.serve(reqs(), n_slots=n_slots, cache_T=cache_T,
                           sched_cfg=sched)
    mismatches = 0
    for a, b in zip(sorted(report.results, key=lambda r: r.request_id),
                    sorted(base.results, key=lambda r: r.request_id)):
        if (len(a.tokens) != len(b.tokens)
                or (np.asarray(a.tokens) != np.asarray(b.tokens)).any()):
            mismatches += 1

    if keep_paths:
        with open(profile_path, "w") as f:
            json.dump({"weights": engine.weight_sparsity_profile(),
                       "measured": report.hw_measured}, f, indent=2,
                      default=float)

    result = {
        "n_requests": n_requests,
        "n_tenants": n_tenants,
        "n_slots": n_slots,
        "mesh_shape": list(mesh_shape) if mesh_shape else None,
        "matmul_backend": engine.executor.matmul_backend,
        "probe_every": probe_every,
        "block_size": block_size,
        "arrival_rate_per_step": rate,
        "prompt_len_min": int(min(len(p) for p in prompts)),
        "prompt_len_max": int(max_prompt),
        # gated: suffix-matched by benchmarks/compare.py
        "per_step_ms": percentiles(step_ms),
        "tokens_per_s": report.decode_tokens_per_s,
        # informative (not gated)
        "prefill_ms_pcts": percentiles(prefill_ms),
        "decode_steps": int(report.steps),
        "n_syncs": int(report.n_syncs),
        "prefix_hit_blocks": int(report.prefix_hit_blocks),
        "drafted_tokens": int(report.drafted_tokens),
        "accepted_tokens": int(report.accepted_tokens),
        "acceptance_rate": (report.accepted_tokens
                            / max(report.drafted_tokens, 1)),
        "n_hw_samples": int(summary.n_hw_samples),
        "hw_measured": report.hw_measured,
        "token_mismatches": mismatches,
    }
    if keep_paths:
        result["telemetry_metrics"] = metrics_path
        result["telemetry_trace"] = trace_path
        result["sparsity_profile"] = profile_path
    if own_tmp is not None:
        own_tmp.cleanup()
    return result


def run_mesh_leg(mesh_shape, *, tiny: bool = False, seed: int = 0,
                 probe_every: int = 2, n_slots: int = None,
                 n_requests: int = None, rate: float = 0.7,
                 block_size: int = 8, telemetry_dir: str = None,
                 matmul_backend: str = None) -> dict:
    """Run the mix on a ``("data", "model")`` mesh.

    On real accelerators this process already holds every chip, so the leg
    runs here over ``jax.devices()``.  Virtual CPU devices need
    ``XLA_FLAGS`` set before jax initializes and the parent's jax is
    already initialized single-device, so there the mesh leg reuses the
    ``benchmarks/sharded_serving.py`` worker harness: spawn this script
    with ``--worker``, parse its last-line JSON."""
    from benchmarks.common import holds_accelerator
    if holds_accelerator():
        return run(tiny=tiny, seed=seed, probe_every=probe_every,
                   n_slots=n_slots, n_requests=n_requests, rate=rate,
                   block_size=block_size, telemetry_dir=telemetry_dir,
                   mesh_shape=mesh_shape, matmul_backend=matmul_backend)
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
           "--seed", str(seed), "--probe-every", str(probe_every),
           "--rate", str(rate), "--block-size", str(block_size)]
    if tiny:
        cmd.append("--tiny")
    if n_slots is not None:
        cmd += ["--slots", str(n_slots)]
    if n_requests is not None:
        cmd += ["--requests", str(n_requests)]
    if telemetry_dir is not None:
        cmd += ["--telemetry", telemetry_dir]
    if matmul_backend is not None:
        cmd += ["--matmul-backend", matmul_backend]
    out = subprocess.run(cmd, capture_output=True, text=True, timeout=1200,
                         env=env)
    if out.returncode != 0:
        raise RuntimeError(
            f"production-mix mesh worker failed:\n{out.stdout}\n{out.stderr}")
    return json.loads(out.stdout.splitlines()[-1])


def _print_summary(r, label=""):
    p = r["per_step_ms"] or {}
    where = (f"mesh {tuple(r['mesh_shape'])}" if r.get("mesh_shape")
             else "single-device")
    print(f"{label}{where}: requests={r['n_requests']} "
          f"tenants={r['n_tenants']} slots={r['n_slots']} "
          f"rate={r['arrival_rate_per_step']}/step "
          f"prompts={r['prompt_len_min']}..{r['prompt_len_max']} tokens")
    print(f"steps: {r['decode_steps']} decode+verify, per-step ms "
          f"p50={p.get('p50', float('nan')):.2f} "
          f"p90={p.get('p90', float('nan')):.2f} "
          f"p99={p.get('p99', float('nan')):.2f}   "
          f"{r['tokens_per_s']:.1f} tok/s")
    print(f"speculation: {r['accepted_tokens']}/{r['drafted_tokens']} "
          f"drafts accepted ({r['acceptance_rate']*100:.0f}%)   "
          f"prefix hits: {r['prefix_hit_blocks']} blocks")
    hw = r["hw_measured"]
    if hw:
        cyc = hw["cycles"]
        print(f"hw probe: {r['n_hw_samples']} samples, "
              f"act_bs={hw['act_bit_sparsity']:.3f} "
              f"w_bs={hw['weight_bit_sparsity']:.3f} "
              f"util={hw['array_utilization']:.3f}, cycles/MAC "
              f"bp_exact={cyc['bp_exact']:.2f} "
              f"bp_approx={cyc['bp_approx']:.2f} "
              f"adas={cyc['adas']:.2f} bitwave={cyc['bitwave']:.2f}")
    if r.get("telemetry_metrics"):
        print(f"telemetry: {r['telemetry_metrics']} + "
              f"{r['telemetry_trace']} + {r['sparsity_profile']}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke size (seconds, not minutes)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--probe-every", type=int, default=2,
                    help="sample every k-th decode/verify step (0 = off)")
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--rate", type=float, default=0.7,
                    help="Poisson arrivals per decode step")
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--telemetry", metavar="DIR", default=None,
                    help="keep metrics JSONL + trace + sparsity profile "
                         "under DIR (otherwise a temp dir is used)")
    ap.add_argument("--mesh", default="2x4",
                    help="mesh shape DATAxMODEL for the tensor-parallel "
                         "leg, or 'none' to skip it")
    ap.add_argument("--matmul-backend", default=None,
                    help="matmul backend override for the mesh leg "
                         "(e.g. kernel_interpret)")
    ap.add_argument("--worker", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    mesh_shape = (None if args.mesh.lower() == "none"
                  else tuple(int(d) for d in args.mesh.lower().split("x")))

    if args.worker:
        r = run(tiny=args.tiny, seed=args.seed,
                probe_every=args.probe_every, n_slots=args.slots,
                n_requests=args.requests, rate=args.rate,
                block_size=args.block_size, telemetry_dir=args.telemetry,
                mesh_shape=mesh_shape, matmul_backend=args.matmul_backend)
        print(json.dumps(r, default=float))
        return 0

    r = run(tiny=args.tiny, seed=args.seed, probe_every=args.probe_every,
            n_slots=args.slots, n_requests=args.requests, rate=args.rate,
            block_size=args.block_size, telemetry_dir=args.telemetry)

    from benchmarks.common import save_artifact
    path = save_artifact("BENCH_production_mix", r)
    _print_summary(r)
    print(f"artifact: {path}")

    rc = 0
    if r["token_mismatches"]:
        print("ERROR: production mix diverged from plain greedy outputs",
              file=sys.stderr)
        rc = 1

    if mesh_shape is not None:
        rm = run_mesh_leg(mesh_shape, tiny=args.tiny, seed=args.seed,
                          probe_every=args.probe_every, n_slots=args.slots,
                          n_requests=args.requests, rate=args.rate,
                          block_size=args.block_size,
                          telemetry_dir=args.telemetry,
                          matmul_backend=args.matmul_backend)
        mesh_path = save_artifact("BENCH_production_mix_mesh", rm)
        print()
        _print_summary(rm, label="mesh leg · ")
        print(f"artifact: {mesh_path}")
        if rm["token_mismatches"]:
            print("ERROR: mesh production mix diverged from plain greedy "
                  "outputs", file=sys.stderr)
            rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
