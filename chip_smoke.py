#!/usr/bin/env python3
"""Prove that the int8 serving path runs on a TPU at qwen2-1.5b's widths.

    python chip_smoke.py             # one chip: phases (a), (b), (c)
    python chip_smoke.py --chips 4   # four chips: phase (d) only

Phases, in order.  Every check that fails exits non-zero before the last
line is printed.

(a) Device.  ``jax.devices()[0]`` must be a TPU, else the script exits 2
    before any model is built.  It never carries on on the CPU.
(b) Kernel parity at real widths, on the chip.  ``bitparticle_matmul``
    exact and approx at M in {8, 512} for the four qwen2-1.5b projections
    against ``core.bp_matmul.bp_matmul_int`` on XLA: int32 accumulators
    equal, dequantized output within ``MATMUL_RTOL``.  The paged-attention
    kernel against its XLA gather oracle (bf16 pages, block 16, G=6, D=128,
    ragged lengths, layer 1 of a two-layer pool) within ``ATTN_ATOL``.
(c) The main path.  qwen2-1.5b at its published widths (28 layers, random
    weights from ``--seed``), quantized to int8, ``bp_exact``, matmul
    backend ``auto``, paged KV cache with float pages and block 16.  One
    ``Replica`` behind the ``FrontDoor``; the blocking ``FrontDoorClient``
    sends the requests over TCP, some streamed.  Fails on an incomplete or
    failed request, a backend fallback, any fault/recover/degrade record, a
    resolved backend other than "kernel", a decode step without a
    ``tpu_custom_call``, or prefill logits further than ``LOGIT_REL_TOL``
    from the same engine's params on the XLA backend.
(d) ``--chips 4``: the same requests on a (2, 2) mesh executor over four
    chips against one chip, in this one process: prefill logits within
    ``MESH_LOGIT_REL_TOL``, sharded weights on all four devices, and the
    kernels inside ``shard_map``.

The last stdout line is ``{"ok": true, "device": {"platform": ...,
"kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# written tolerances (see the phase descriptions above)
MATMUL_RTOL = 1e-6         # dequant epilogue: same f32 ops in the same order
ATTN_ATOL = 2e-2           # bf16 pages and bf16 output of O(1) values
# Relative l2 of prefill logits.  Two programs that round one f32 op in a
# different order differ by ~4e-2 after 28 layers of random weights (the
# one-ulp differences flip bf16 and int8 roundings, and the layers amplify
# them); logits of a wrong kernel are uncorrelated, ~1.4.
LOGIT_REL_TOL = 1e-1       # ||kernel - xla|| / ||xla||
MESH_LOGIT_REL_TOL = 1e-1  # ||mesh - single|| / ||single||

PROJECTIONS = ((1536, 1536), (1536, 256), (1536, 8960), (8960, 1536))
PROMPT_LENS = (128, 160, 192, 256, 320, 384, 448, 512)
NEW_TOKENS = 32
BLOCK_SIZE = 16


class CheckFailed(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


def log(msg: str) -> None:
    print(msg, flush=True)


# -- (a) device ----------------------------------------------------------------

def device_check(chips: int):
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (first device is {dev.platform!r});"
              f" refusing to run on it", file=sys.stderr)
        sys.exit(2)
    if len(devices) < chips:
        print(f"chip_smoke: --chips {chips} needs {chips} devices, found "
              f"{len(devices)}", file=sys.stderr)
        sys.exit(2)
    log(f"(a) device: {dev.device_kind} x{len(devices)} ({dev.platform})")
    return dev


# -- (b) kernel parity -----------------------------------------------------------

def matmul_parity(seed: int, *, interpret: bool = False,
                  projections=PROJECTIONS, ms=(8, 512)) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.bp_matmul import bp_matmul_int
    from repro.kernels.bitparticle_matmul.ops import bp_matmul

    rng = np.random.default_rng(seed)
    ref_int = jax.jit(bp_matmul_int, static_argnums=2)
    for m in ms:
        for k, n in projections:
            a = jnp.asarray(rng.integers(-127, 128, (m, k)), jnp.int8)
            w = jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int8)
            sa = jnp.asarray(rng.random((m, 1)) * 0.02 + 1e-3, jnp.float32)
            sw = jnp.asarray(rng.random((n,)) * 0.02 + 1e-3, jnp.float32)
            for mode in ("bp_exact", "bp_approx"):
                approx = mode == "bp_approx"
                want = np.asarray(ref_int(a, w, mode))
                got = np.asarray(bp_matmul(a, w, approx=approx,
                                           interpret=interpret))
                check(got.dtype == np.int32 and np.array_equal(got, want),
                      f"bp_matmul {mode} M={m} {k}->{n}: int32 accumulators "
                      f"differ from bp_matmul_int")
                deq = np.asarray(bp_matmul(a, w, sa, sw, approx=approx,
                                           interpret=interpret))
                ref = want.astype(np.float32) * np.asarray(sa) * np.asarray(sw)
                err = float(np.max(np.abs(deq - ref) / np.maximum(
                    np.abs(ref), 1e-30)))
                check(err <= MATMUL_RTOL,
                      f"bp_matmul {mode} M={m} {k}->{n}: dequant rel err "
                      f"{err} > {MATMUL_RTOL}")
    log(f"(b) bp_matmul exact+approx M={list(ms)} x {len(projections)} "
        f"projections: int32 equal, dequant within rtol {MATMUL_RTOL}")


def attention_parity(seed: int, *, interpret: bool = False, B=8, KH=2, G=6,
                     D=128, bs=BLOCK_SIZE, pages_per_seq=32) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.paged_attention.kernel import paged_attention_kernel
    from repro.kernels.paged_attention.ref import paged_attention_xla

    rng = np.random.default_rng(seed)
    n_blocks = B * pages_per_seq + 1          # block 0 is the trash page
    q = jnp.asarray(rng.normal(size=(B, KH * G, D)), jnp.bfloat16)
    # the stacked token-major pool of two layers; the kernel reads layer 1
    kp = jnp.asarray(rng.normal(size=(2, n_blocks, bs, KH * D)), jnp.bfloat16)
    vp = jnp.asarray(rng.normal(size=(2, n_blocks, bs, KH * D)), jnp.bfloat16)
    tables = np.zeros((B, pages_per_seq), np.int32)
    perm = rng.permutation(np.arange(1, n_blocks))
    lengths = rng.integers(0, pages_per_seq * bs, size=B).astype(np.int32)
    lengths[0], lengths[-1] = 0, pages_per_seq * bs - 1   # both extremes
    for b in range(B):
        used = lengths[b] // bs + 1
        tables[b, :used] = perm[b * pages_per_seq:b * pages_per_seq + used]
    tables, lengths = jnp.asarray(tables), jnp.asarray(lengths)
    got = paged_attention_kernel(q, kp, vp, tables, lengths, 1,
                                 interpret=interpret)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(paged_attention_xla)(q.astype(jnp.float32), kp, vp,
                                            tables, lengths, 1)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
    check(bool(jnp.isfinite(got).all()), "paged_attention: non-finite output")
    check(err <= ATTN_ATOL, f"paged_attention kernel vs XLA oracle: max abs "
                            f"diff {err} > {ATTN_ATOL}")
    log(f"(b) paged_attention bf16 pages bs={bs} G={G} D={D} ragged lengths "
        f"{lengths.tolist()}: max abs diff {err:.3e} (tol {ATTN_ATOL})")


# -- (c) main path ---------------------------------------------------------------

def build_model(cfg, seed: int):
    """Random weights from ``seed``, quantized to int8 leaf by leaf; the
    bf16 originals are dropped once quantized."""
    import jax
    from repro.models import api
    from repro.models.layers import quantize_dense_params
    params = jax.jit(lambda key: api.init(key, cfg))(jax.random.PRNGKey(seed))
    qparams = quantize_dense_params(params)
    del params
    jax.block_until_ready(qparams)
    return qparams


def make_prompts(seed: int, vocab: int, lens=PROMPT_LENS):
    import numpy as np
    rng = np.random.default_rng(seed + 1)
    return [rng.integers(2, vocab, size=n).astype(np.int32) for n in lens]


def serve_config():
    from repro.serving import ServeConfig
    return ServeConfig(max_new_tokens=NEW_TOKENS, temperature=0.0,
                       cache_backend="paged", block_size=BLOCK_SIZE)


def sched_config():
    from repro.serving import SchedulerConfig
    # one request per prefill: the compiled prefill shapes are one per
    # power-of-two prompt bucket, independent of arrival timing
    return SchedulerConfig(max_prefill_batch=1)


def prefill_logits(executor, prompt, loop):
    """Last-position logits of one prompt through the executor's ragged
    prefill, shaped exactly as ``loop`` shapes its admissions."""
    import numpy as np
    from repro.serving.scheduler import prefill_bucket_len
    n = len(prompt)
    toks = np.zeros((1, prefill_bucket_len(n, loop.cm.cache_T)), np.int32)
    toks[0, :n] = prompt
    logits, _ = executor.prefill({"tokens": toks}, loop.cache_T,
                                 prompt_lens=np.asarray([n], np.int32))
    return np.asarray(logits, np.float32)[0]


def rel_err(got, want) -> float:
    import numpy as np
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def loop_health(loop, engine, expect_backend: str) -> None:
    from repro.core.bp_matmul import backend_fallbacks, resolve_matmul_backend
    bad = [r for r in loop.stream
           if r["kind"] in ("fault", "recover", "degrade")]
    check(not bad, f"fault/recover/degrade records: {bad[:3]}")
    check(not backend_fallbacks(),
          f"backend fallbacks: {backend_fallbacks()}")
    resolved = resolve_matmul_backend(engine.executor.matmul_backend)
    check(resolved == expect_backend,
          f"resolved backend {resolved!r}, expected {expect_backend!r}")


def decode_step_text(loop):
    """Compiled text of the loop's decode step at its live shapes."""
    import jax.numpy as jnp
    cm = loop.cm
    n = loop.n_slots
    step = {"tokens": jnp.zeros((n, 1), jnp.int32),
            "cache_len": cm.cache_len_vector(),
            "block_tables": cm.block_tables_device()}
    lowered = loop._decode_fn.lower(cm.cache, step,
                                    jnp.zeros((n, 2), jnp.uint32),
                                    jnp.zeros((n,), jnp.uint32))
    return lowered.as_text(), lowered.compile().as_text()


def main_path(cfg, qparams, seed: int, *, expect_backend: str = "kernel",
              n_slots: int = 8, prompt_lens=PROMPT_LENS,
              new_tokens: int = NEW_TOKENS) -> dict:
    import jax
    import numpy as np
    from repro.serving import (FrontDoor, FrontDoorClient, Replica,
                               ServingEngine, SingleDeviceExecutor)
    from repro.serving.scheduler import prefill_bucket_len

    t0 = time.perf_counter()
    engine = ServingEngine(cfg, qparams, dataclasses.replace(
        serve_config(), max_new_tokens=new_tokens))
    cache_T = max(prompt_lens) + new_tokens + engine.serve_cfg.cache_margin
    replica = Replica(engine, name="r0", n_slots=n_slots, cache_T=cache_T,
                      sched_cfg=sched_config())
    door = FrontDoor([replica]).start()
    prompts = make_prompts(seed, cfg.vocab_size, prompt_lens)
    buckets = sorted({prefill_bucket_len(n, replica.loop.cm.cache_T)
                      for n in prompt_lens})
    warm = make_prompts(seed + 7, cfg.vocab_size, buckets)
    outs = []
    try:
        client = FrontDoorClient("127.0.0.1", door.port, timeout_s=1200)
        check(client.healthz().get("ok") is True, "front door health check")
        t1 = time.perf_counter()
        # compile: one request per prefill bucket, two tokens each (the
        # second token compiles the decode step)
        for p in warm:
            out = client.generate(p.tolist(), max_new_tokens=2)
            check(out["finish_reason"] == "length",
                  f"warm-up request finished {out['finish_reason']!r}")
        t2 = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(len(prompts)) as pool:
            futs = [pool.submit(client.generate, p.tolist(),
                                max_new_tokens=new_tokens,
                                stream=(i % 3 == 0))
                    for i, p in enumerate(prompts)]
            outs = [f.result() for f in futs]
        t3 = time.perf_counter()
    finally:
        door.stop()
    loop = replica.loop
    n_streamed = len(range(0, len(prompts), 3))
    for i, out in enumerate(outs):
        check(out["finish_reason"] == "length"
              and len(out["tokens"]) == new_tokens,
              f"request {i}: finish_reason={out['finish_reason']!r}, "
              f"{len(out['tokens'])}/{new_tokens} tokens")
    check(len(outs) == len(prompts), "not every request completed")
    loop_health(loop, engine, expect_backend)
    n_tokens = sum(len(o["tokens"]) for o in outs)
    log(f"(c) served {len(outs)} requests over TCP ({n_streamed} streamed), "
        f"prompts {min(prompt_lens)}-{max(prompt_lens)} tokens, "
        f"{new_tokens} new tokens each: all finished 'length'")

    lowered_text, compiled_text = decode_step_text(loop)
    n_calls = compiled_text.count("tpu_custom_call")
    names = [k for k in ("paged_attention_decode", "bitparticle_matmul_exact")
             if k in lowered_text]
    if expect_backend == "kernel":
        check(n_calls > 0, "compiled decode step has no tpu_custom_call")
        check(len(names) == 2, f"decode step kernels found: {names}")
    log(f"(c) compiled decode step: {n_calls} tpu_custom_call mentions, "
        f"kernels {names}")

    stats = jax.devices()[0].memory_stats() or {}
    result = {"setup_s": t1 - t0, "compile_s": t2 - t1, "serve_s": t3 - t2,
              "tokens": n_tokens,
              "peak_bytes_in_use": stats.get("peak_bytes_in_use")}
    log(f"(c) set-up {result['setup_s']:.3f} s, compile (warm-up requests) "
        f"{result['compile_s']:.3f} s, serve {result['serve_s']:.3f} s, "
        f"{n_tokens} tokens generated, peak_bytes_in_use "
        f"{result['peak_bytes_in_use']}")

    xla = SingleDeviceExecutor(cfg, engine.params, matmul_backend="xla")
    lk = prefill_logits(engine.executor, prompts[0], loop)
    lx = prefill_logits(xla, prompts[0], loop)
    result["logit_rel_err"] = err = rel_err(lk, lx)
    log(f"(c) prefill logits kernel vs xla, prompt of {len(prompts[0])}: "
        f"rel l2 {err:.3e} (tol {LOGIT_REL_TOL}), max abs "
        f"{float(np.max(np.abs(lk - lx))):.3e}, greedy token "
        f"{'matches' if lk.argmax() == lx.argmax() else 'differs'} "
        f"({int(lk.argmax())} vs {int(lx.argmax())})")
    check(err <= LOGIT_REL_TOL,
          f"prefill logits kernel vs xla rel err {err} > {LOGIT_REL_TOL}")
    return result


# -- (d) four chips --------------------------------------------------------------

def mesh_phase(cfg, qparams, seed: int, *, expect_backend: str = "kernel",
               mesh_shape=(2, 2), n_slots: int = 8,
               prompt_lens=PROMPT_LENS, new_tokens: int = NEW_TOKENS) -> dict:
    import jax
    import numpy as np
    from repro.serving import Request, ServingEngine

    n_dev = mesh_shape[0] * mesh_shape[1]
    devices = set(jax.devices()[:n_dev])
    sc = dataclasses.replace(serve_config(), max_new_tokens=new_tokens)
    single = ServingEngine(cfg, qparams, sc)
    mesh = ServingEngine(cfg, qparams,
                         dataclasses.replace(sc, mesh_shape=mesh_shape))
    n_sharded = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            mesh.executor.params):
        spec = leaf.sharding.spec
        if "model" not in tuple(spec):
            continue
        n_sharded += 1
        on = {s.device for s in leaf.addressable_shards}
        check(on == devices, f"{jax.tree_util.keystr(path)} lives on "
                             f"{len(on)} of {n_dev} devices")
        ax = tuple(spec).index("model")
        shard_dim = leaf.addressable_shards[0].data.shape[ax]
        check(shard_dim * mesh_shape[1] == leaf.shape[ax],
              f"{jax.tree_util.keystr(path)}: shard dim {shard_dim} of "
              f"{leaf.shape[ax]}")
    check(n_sharded > 0, "no parameter is sharded over 'model'")
    log(f"(d) {n_sharded} weight leaves sharded over 'model', each on all "
        f"{n_dev} devices")

    prompts = make_prompts(seed, cfg.vocab_size, prompt_lens)
    cache_T = max(prompt_lens) + new_tokens + sc.cache_margin
    tokens, loops, secs = {}, {}, {}
    for name, engine in (("single", single), ("mesh", mesh)):
        reqs = [Request(prompt=p, max_new_tokens=new_tokens)
                for p in prompts]
        loop = engine.make_loop(reqs, n_slots=n_slots, cache_T=cache_T,
                                sched_cfg=sched_config())
        t0 = time.perf_counter()
        report = loop.run()
        secs[name] = time.perf_counter() - t0
        check(all(r.finish_reason == "length" for r in report.results),
              f"{name}: not every request finished 'length'")
        loop_health(loop, engine, expect_backend)
        tokens[name] = [list(r.tokens) for r in
                        sorted(report.results, key=lambda r: r.request_id)]
        loops[name] = loop
    same = sum(a == b for a, b in zip(tokens["single"], tokens["mesh"]))
    first = sum(a[0] == b[0] for a, b in zip(tokens["single"],
                                               tokens["mesh"]))
    log(f"(d) tokens mesh vs single: {same}/{len(prompts)} sequences "
        f"identical, {first}/{len(prompts)} first tokens equal "
        f"(serve incl. compile: single {secs['single']:.1f} s, mesh "
        f"{secs['mesh']:.1f} s)")

    ls = prefill_logits(single.executor, prompts[0], loops["single"])
    lm = prefill_logits(mesh.executor, prompts[0], loops["mesh"])
    err = rel_err(lm, ls)
    log(f"(d) prefill logits mesh vs single: rel l2 {err:.3e} "
        f"(tol {MESH_LOGIT_REL_TOL}), max abs "
        f"{float(np.max(np.abs(lm - ls))):.3e}")
    check(err <= MESH_LOGIT_REL_TOL,
          f"mesh prefill logits rel err {err} > {MESH_LOGIT_REL_TOL}")

    lowered_text, compiled_text = decode_step_text(loops["mesh"])
    manual = ("sdy.manual_computation" in lowered_text
              or "SPMDFullToShardShape" in lowered_text)
    n_calls = compiled_text.count("tpu_custom_call")
    check(manual, "mesh decode step has no shard_map region")
    if expect_backend == "kernel":
        check(n_calls > 0, "mesh decode step has no tpu_custom_call")
    log(f"(d) mesh decode step: shard_map regions present, {n_calls} "
        f"tpu_custom_call mentions")
    return {"logit_rel_err": err, "identical_sequences": same}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the (2, 2) mesh phase and its "
                         "one-chip comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    # the TPU runtime logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    dev = device_check(args.chips)
    log(f"compile cache: {cache_dir}")

    from repro.configs.base import get_arch
    cfg = get_arch("qwen2-1.5b").replace(matmul_mode="bp_exact",
                                         matmul_backend="auto")
    t0 = time.perf_counter()
    try:
        if args.chips == 1:
            matmul_parity(args.seed)
            attention_parity(args.seed)
            log(f"(b) kernel parity: {time.perf_counter() - t0:.1f} s")
        t1 = time.perf_counter()
        qparams = build_model(cfg, args.seed)
        log(f"model: {cfg.name} {cfg.num_layers} layers d_model "
            f"{cfg.d_model} vocab {cfg.vocab_size}, random init + int8 "
            f"quantization {time.perf_counter() - t1:.1f} s")
        if args.chips == 1:
            main_path(cfg, qparams, args.seed)
        else:
            mesh_phase(cfg, qparams, args.seed)
    except CheckFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
