"""End-to-end serving driver: a stream of requests with Poisson arrivals
against a small qwen2-family model with BitParticle W8A8 weights and an int8
KV cache, served by the quasi-sync continuous-batching engine.

    PYTHONPATH=src python examples/serve_lm.py [--tokens 24] [--requests 8]
    PYTHONPATH=src python examples/serve_lm.py --mode bf16 --lead-window 0
    PYTHONPATH=src python examples/serve_lm.py --mesh 2x4   # TP over a mesh
    PYTHONPATH=src python examples/serve_lm.py --draft prompt_lookup
    PYTHONPATH=src python examples/serve_lm.py --draft model \
        --num-draft-tokens 4                  # speculative decoding
    PYTHONPATH=src python examples/serve_lm.py \
        --metrics run.jsonl --trace trace.json   # observability sinks
    PYTHONPATH=src python examples/serve_lm.py --probe 2 \
        --metrics run.jsonl   # measured bit-sparsity -> hw_estimate records
"""

import argparse
import os
import sys


def _parse_mesh(argv):
    """(data, model) from a ``--mesh DxM`` argument, or None.  Validates
    here (this runs before argparse, which only exists post-jax-init)."""
    for i, a in enumerate(argv):
        if a == "--mesh" and i + 1 < len(argv):
            val = argv[i + 1]
        elif a.startswith("--mesh="):
            val = a.split("=", 1)[1]
        else:
            continue
        parts = val.lower().split("x")
        if len(parts) != 2 or not all(p.isdigit() and int(p) > 0
                                      for p in parts):
            sys.exit(f"serve_lm: --mesh expects DATAxMODEL (e.g. 2x4), "
                     f"got {val!r}")
        return tuple(int(p) for p in parts)
    return None


# --mesh needs the virtual devices to exist BEFORE jax initializes its
# backend (device count is locked at first init), so this runs pre-import.
# The flag only affects the host/CPU platform; on real accelerators the
# mesh lays over the physical devices.
_MESH = _parse_mesh(sys.argv[1:])
if _MESH is not None and "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={_MESH[0] * _MESH[1]}")

import numpy as np
import jax

from repro.configs.base import get_arch
from repro.launch.compile_cache import enable_compile_cache
from repro.models import api
from repro.models.layers import quantize_dense_params
from repro.serving import (Request, SchedulerConfig, ServeConfig,
                           ServingEngine, SparsityProbe, Telemetry)


def main():
    # allow_abbrev=False: the pre-import XLA-flag scanner above only
    # recognizes the full `--mesh` spelling, so abbreviations must not
    # silently parse here with the devices never spawned
    ap = argparse.ArgumentParser(allow_abbrev=False)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=24,
                    help="max new tokens per request")
    ap.add_argument("--rate", type=float, default=0.3,
                    help="Poisson arrivals per decode step")
    ap.add_argument("--lead-window", type=int, default=4,
                    help="admission lead window E (0 = sync every step)")
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--mode", default="bp_exact",
                    choices=["bf16", "bp_exact", "bp_approx"])
    ap.add_argument("--cache-backend", default="slab",
                    choices=["slab", "paged"],
                    help="decode-cache store: worst-case slab slots or "
                         "on-demand KV blocks with prefix sharing")
    ap.add_argument("--block-size", type=int, default=16,
                    help="tokens per KV block (paged backend)")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="serve tensor-parallel over a (data, model) mesh, "
                         "e.g. 2x4 (spawns virtual CPU devices off-TPU)")
    ap.add_argument("--draft", default="none",
                    choices=["none", "prompt_lookup", "model"],
                    help="speculative decoding drafter: weight-free n-gram "
                         "prompt lookup, or a half-size same-family draft "
                         "model (greedy only — forces temperature 0)")
    ap.add_argument("--num-draft-tokens", type=int, default=4,
                    help="K: draft tokens verified per decode step")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="append one JSONL record per serving step "
                         "(docs/observability.md)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome-trace JSON of serving spans — "
                         "load it in https://ui.perfetto.dev")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="capture a jax.profiler device trace into DIR "
                         "(view with tensorboard or perfetto)")
    ap.add_argument("--probe", type=int, default=0, metavar="K",
                    help="sample measured activation bit sparsity every "
                         "K-th decode step (0 = off) and fold it through "
                         "the paper's cost models — needs a bp_* --mode; "
                         "emits hw_estimate records when --metrics is set")
    args = ap.parse_args()
    enable_compile_cache()
    mesh_shape = _MESH     # parsed+validated pre-import (sets XLA_FLAGS)
    if args.draft != "none" and args.temperature > 0:
        print(f"--draft {args.draft}: speculative decoding is greedy-only, "
              f"forcing --temperature 0")
        args.temperature = 0.0

    cfg = get_arch("qwen2-1.5b").reduced().replace(
        num_layers=4, d_model=256, d_ff=512, vocab_size=2048, head_dim=32)
    print(f"arch: qwen2-family reduced ({cfg.param_count()/1e6:.1f}M params), "
          f"mode={args.mode}")

    params = api.init(jax.random.PRNGKey(0), cfg)
    if args.mode != "bf16":
        params = quantize_dense_params(params)
        cfg = cfg.replace(matmul_mode=args.mode, kv_cache_int8=True)
        print("weights quantized to int8 (per-channel), KV cache int8")

    draft_cfg = draft_params = None
    if args.draft == "model":
        # half-size same-family drafter (qwen2-1.5b drafting for the larger
        # target, in spirit); random-init weights -> modest acceptance, the
        # machinery and accounting are what this example shows
        draft_cfg = cfg.replace(num_layers=2, d_model=128, d_ff=256,
                                head_dim=32)
        draft_params = api.init(jax.random.PRNGKey(7), draft_cfg)
        if args.mode != "bf16":
            draft_params = quantize_dense_params(draft_params)

    probe = None
    if args.probe > 0:
        if args.mode == "bf16":
            sys.exit("serve_lm: --probe taps int8 operands; use a bp_* "
                     "--mode")
        probe = SparsityProbe(probe_every=args.probe)

    engine = ServingEngine(cfg, params,
                           ServeConfig(max_new_tokens=args.tokens,
                                       temperature=args.temperature,
                                       cache_backend=args.cache_backend,
                                       block_size=args.block_size,
                                       mesh_shape=mesh_shape,
                                       draft=args.draft,
                                       num_draft_tokens=args.num_draft_tokens,
                                       probe=probe),
                           draft_cfg=draft_cfg, draft_params=draft_params)
    if mesh_shape is not None:
        print(f"mesh executor: {mesh_shape[0]}x{mesh_shape[1]} "
              f"(data, model) over {len(jax.devices())} devices — weights "
              f"TP-sharded, KV cache split per the decode recipe")

    rng = np.random.default_rng(0)
    prompts = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1),
                           (args.requests, args.prompt_len), 2,
                           cfg.vocab_size), np.int32)
    lo = min(max(1, args.tokens // 4), args.tokens)
    max_news = rng.integers(lo, args.tokens + 1, size=args.requests)
    arrivals = np.cumsum(rng.exponential(1.0 / args.rate, args.requests))
    requests = [Request(prompt=prompts[i], max_new_tokens=int(max_news[i]),
                        arrival_time=float(arrivals[i]))
                for i in range(args.requests)]

    # warmup (compile prefill + vector-cache_len decode) — runs BEFORE the
    # telemetry handle is attached so the sinks see only the measured serve
    engine.serve([Request(prompt=prompts[0], max_new_tokens=2)],
                 n_slots=args.slots,
                 cache_T=args.prompt_len + args.tokens
                 + engine.serve_cfg.cache_margin)

    tel = None
    if args.metrics or args.trace or args.profile_dir:
        import dataclasses
        tel = Telemetry(metrics_path=args.metrics, trace_path=args.trace,
                        profile_dir=args.profile_dir)
        engine.serve_cfg = dataclasses.replace(engine.serve_cfg,
                                               telemetry=tel)

    report = engine.serve(
        requests, n_slots=args.slots,
        cache_T=args.prompt_len + args.tokens + engine.serve_cfg.cache_margin,
        sched_cfg=SchedulerConfig(lead_window=args.lead_window))
    if tel is not None:
        tel.close()
        sinks = [p for p in (args.metrics, args.trace, args.profile_dir) if p]
        print(f"telemetry: {', '.join(sinks)}")

    print(f"\nserved {args.requests} requests on {args.slots} slots "
          f"(E={args.lead_window}, Poisson rate {args.rate}/step)")
    print(f"prefill: {report.prefill_s*1e3:.1f} ms across "
          f"{report.n_syncs} admission syncs")
    print(f"decode:  {report.steps} batched steps, "
          f"{report.decode_tokens_per_s:.1f} tokens/s, "
          f"{report.slot_utilization*100:.0f}% slot utilization, "
          f"max position divergence {report.max_divergence}")
    if report.cache_backend == "paged":
        print(f"paged:   peak {report.peak_blocks_in_use} blocks in use, "
              f"{report.prefix_hit_blocks} prefix-hit blocks, "
              f"{report.cow_blocks} copy-on-writes, "
              f"{report.n_preemptions} preemptions")
    if report.draft != "none":
        print(f"spec:    drafter={report.draft} "
              f"K={args.num_draft_tokens}: "
              f"{report.accepted_tokens}/{report.drafted_tokens} drafts "
              f"accepted ({report.acceptance_rate*100:.0f}%), "
              f"{report.committed_tokens_per_step:.2f} committed "
              f"tokens/step")
    if report.ttft_wall is not None:
        itl = (f", itl p50 {report.itl_wall['p50']*1e3:.1f} ms "
               f"p99 {report.itl_wall['p99']*1e3:.1f} ms"
               if report.itl_wall else "")
        print(f"latency: ttft p50 {report.ttft_wall['p50']*1e3:.1f} ms "
              f"p99 {report.ttft_wall['p99']*1e3:.1f} ms{itl}")
    for r in report.results[:4]:
        print(f"  req {r.request_id}: {len(r.tokens)} tokens "
              f"(ttft {r.ttft_steps:.0f} steps, "
              f"latency {r.latency_steps:.0f} steps, {r.finish_reason}) "
              f"head: {r.tokens[:8].tolist()}")

    # ---- BitParticle deployment estimate ----------------------------------
    if report.deployment is not None:
        d = report.deployment
        print(f"\nBitParticle deployment estimate (modeled 45nm array, "
              f"{d['mode']}):")
        print(f"  mean weight bit sparsity (sign-magnitude): "
              f"{d['mean_bit_sparsity']:.3f}")
        print(f"  mean cycles/MAC: {d['mean_cycles_per_mac']:.2f}   "
              f"mean energy/MAC: {d['mean_mac_energy_pj']:.2f} pJ")
        for e in d["per_layer"][:6]:
            name = f"layer {e['layer']}" if e["layer"] >= 0 else "unstacked"
            print(f"    {name}: bs={e['bit_sparsity']:.3f} "
                  f"cycles={e['avg_cycles_per_mac']:.2f} "
                  f"energy={e['mac_energy_pj']:.2f} pJ")

    # ---- measured-traffic hardware estimate (--probe) ---------------------
    if report.hw_measured is not None:
        hw = report.hw_measured
        print(f"\nmeasured-traffic hardware estimate "
              f"({hw['n_samples']} sampled steps, every "
              f"{hw['probe_every']}):")
        print(f"  activation bit sparsity {hw['act_bit_sparsity']:.3f} "
              f"(value {hw['act_value_sparsity']:.3f}), weight bit "
              f"sparsity {hw['weight_bit_sparsity']:.3f}")
        print(f"  modeled array utilization "
              f"{hw['array_utilization']:.3f}")
        for m in sorted(hw["cycles"]):
            print(f"    {m}: {hw['cycles'][m]:.2f} cycles/MAC, "
                  f"{hw['mac_energy_pj'][m]:.2f} pJ/MAC")


if __name__ == "__main__":
    main()
