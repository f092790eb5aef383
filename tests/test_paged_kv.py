"""Paged KV-cache backend vs the slab backend: greedy token-identity across
workloads (staggered, heterogeneous, shared-prefix, int8 KV, MoE),
prefix-sharing block savings, copy-on-write, preemption-and-requeue, and the
power-of-two prefill bucketing satellite."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs.base import get_arch
from repro.models import api
from repro.serving import (QuasiSyncScheduler, Request, RequestQueue,
                           SchedulerConfig, ServeConfig, ServingEngine,
                           make_cache_manager)
from repro.serving.scheduler import prefill_bucket_len

jax.config.update("jax_default_matmul_precision", "float32")


def _dense_cfg(**kw):
    return get_arch("qwen2-1.5b").reduced().replace(
        num_layers=2, d_model=64, d_ff=128, vocab_size=128, head_dim=16, **kw)


def _engine(cfg, backend, max_new=8, block_size=4, eos=None, seed=0):
    params = api.init(jax.random.PRNGKey(seed), cfg)
    return ServingEngine(cfg, params,
                         ServeConfig(max_new_tokens=max_new, temperature=0.0,
                                     eos_id=eos, cache_backend=backend,
                                     block_size=block_size))


def _prompts(cfg, B, S, seed=1):
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(seed), (B, S), 2,
                           cfg.vocab_size), np.int32)


def _assert_same_results(report_a, report_b):
    ra = sorted(report_a.results, key=lambda r: r.request_id)
    rb = sorted(report_b.results, key=lambda r: r.request_id)
    for a, b in zip(ra, rb):
        assert a.finish_reason == b.finish_reason
        assert len(a.tokens) == len(b.tokens), (a.tokens, b.tokens)
        np.testing.assert_array_equal(a.tokens, b.tokens)


def _both(cfg, reqs_fn, *, max_new=8, eos=None, seed=0, **serve_kw):
    slab = _engine(cfg, "slab", max_new=max_new, eos=eos, seed=seed)
    paged = _engine(cfg, "paged", max_new=max_new, eos=eos, seed=seed)
    r_slab = slab.serve(reqs_fn(), **{k: v for k, v in serve_kw.items()
                                      if k != "num_blocks"})
    r_paged = paged.serve(reqs_fn(), **serve_kw)
    _assert_same_results(r_slab, r_paged)
    return r_slab, r_paged


# ---------------------------------------------------------------------------
# Token identity: paged must reproduce the slab outputs exactly
# ---------------------------------------------------------------------------

class TestPagedTokenIdentity:
    def test_simultaneous_arrivals(self):
        cfg = _dense_cfg()
        prompts = _prompts(cfg, 4, 6)
        _both(cfg, lambda: [Request(prompt=prompts[i], max_new_tokens=8)
                            for i in range(4)], n_slots=4)

    def test_staggered_hetero_lengths(self):
        cfg = _dense_cfg()
        prompts = _prompts(cfg, 5, 6)
        max_news = [8, 3, 8, 5, 1]
        _both(cfg,
              lambda: [Request(prompt=prompts[i], max_new_tokens=max_news[i],
                               arrival_time=float(i)) for i in range(5)],
              n_slots=2, sched_cfg=SchedulerConfig(lead_window=2))

    def test_hetero_prompt_lengths(self):
        cfg = _dense_cfg()
        lens = [3, 7, 5, 9]
        prompts = [_prompts(cfg, 1, L, seed=L)[0] for L in lens]
        _both(cfg,
              lambda: [Request(prompt=prompts[i], max_new_tokens=5,
                               arrival_time=float(i)) for i in range(4)],
              n_slots=2)

    def test_eos_early_exit(self):
        cfg = _dense_cfg()
        prompts = _prompts(cfg, 3, 5)
        probe = _engine(cfg, "slab").generate(
            {"tokens": jnp.asarray(prompts)}, max_new_tokens=4)
        eos = int(np.asarray(probe.tokens)[0, -1])   # hit by request 0
        _both(cfg, lambda: [Request(prompt=prompts[i], max_new_tokens=8)
                            for i in range(3)],
              n_slots=3, eos=eos)

    def test_int8_kv_cache(self):
        cfg = _dense_cfg(kv_cache_int8=True)
        prompts = _prompts(cfg, 3, 7)
        _both(cfg, lambda: [Request(prompt=prompts[i], max_new_tokens=5,
                                    arrival_time=float(i)) for i in range(3)],
              n_slots=2)

    def test_moe_family(self):
        cfg = get_arch("granite-moe-1b-a400m").reduced().replace(
            num_layers=2, d_model=64, vocab_size=128, head_dim=16)
        prompts = _prompts(cfg, 3, 6)
        _both(cfg, lambda: [Request(prompt=prompts[i], max_new_tokens=4,
                                    arrival_time=float(i)) for i in range(3)],
              n_slots=2)

    def test_matches_static_generate(self):
        cfg = _dense_cfg()
        engine = _engine(cfg, "paged")
        prompts = _prompts(cfg, 4, 6)
        report = engine.serve([Request(prompt=prompts[i], max_new_tokens=6)
                               for i in range(4)], n_slots=4)
        static = engine.generate({"tokens": jnp.asarray(prompts)},
                                 max_new_tokens=6)
        for i, r in enumerate(sorted(report.results,
                                     key=lambda r: r.request_id)):
            np.testing.assert_array_equal(r.tokens, np.asarray(static.tokens[i]))


# ---------------------------------------------------------------------------
# Memory behavior: sharing, CoW, preemption, elastic admission
# ---------------------------------------------------------------------------

class TestPagedMemoryBehavior:
    def test_shared_prefix_saves_blocks_and_hits_counted(self):
        cfg = _dense_cfg()
        sys_prompt = _prompts(cfg, 1, 12, seed=9)[0]
        uniq = _prompts(cfg, 4, 3, seed=10)
        prompts = [np.concatenate([sys_prompt, uniq[i]]) for i in range(4)]
        reqs = lambda: [Request(prompt=prompts[i], max_new_tokens=4,
                                arrival_time=float(2 * i)) for i in range(4)]
        _, rp = _both(cfg, reqs, max_new=4, n_slots=4)
        assert rp.prefix_hit_blocks > 0
        # 3 followers x 3 shared full blocks of 4 tokens each
        assert rp.prefix_hit_blocks >= 9
        unique_ids = _engine(cfg, "paged", max_new=4).serve(
            [Request(prompt=_prompts(cfg, 1, 15, seed=20 + i)[0],
                     max_new_tokens=4, arrival_time=float(2 * i))
             for i in range(4)], n_slots=4)
        assert rp.peak_blocks_in_use < unique_ids.peak_blocks_in_use

    def test_partial_prefix_copy_on_write(self):
        cfg = _dense_cfg()
        base = _prompts(cfg, 1, 16, seed=5)[0]
        prompts = [base, base[:14]]     # 14 = 3 full blocks + 2-token tail
        _, rp = _both(cfg,
                      lambda: [Request(prompt=prompts[i], max_new_tokens=6,
                                       arrival_time=float(3 * i))
                               for i in range(2)],
                      max_new=6, n_slots=2)
        assert rp.cow_blocks >= 1

    def test_pool_dry_preempts_and_replays(self):
        cfg = _dense_cfg()
        prompts = _prompts(cfg, 3, 8, seed=3)
        reqs = lambda: [Request(prompt=prompts[i], max_new_tokens=8,
                                arrival_time=0.0) for i in range(3)]
        _, rp = _both(cfg, reqs, max_new=8, n_slots=3, cache_T=24,
                      num_blocks=9)
        assert rp.n_preemptions > 0
        assert all(r.finish_reason in ("eos", "length") for r in rp.results)

    def test_admission_is_block_elastic_not_worst_case(self):
        """At a fixed HBM budget a shared-prefix workload admits more
        concurrently on paged than the slab's worst-case reservation."""
        cfg = _dense_cfg()
        sys_prompt = _prompts(cfg, 1, 12, seed=9)[0]
        uniq = _prompts(cfg, 6, 2, seed=11)
        prompts = [np.concatenate([sys_prompt, uniq[i]]) for i in range(6)]
        # budget: 2 slab slots' worth of tokens (2 * 32 = 64 tokens)
        cache_T = 14 + 8 + 8   # prompt + new + margin -> rounds to 32
        reqs = lambda: [Request(prompt=prompts[i], max_new_tokens=8,
                                arrival_time=float(i)) for i in range(6)]
        slab = _engine(cfg, "slab").serve(reqs(), n_slots=2, cache_T=cache_T)
        paged = _engine(cfg, "paged").serve(
            reqs(), n_slots=6, cache_T=cache_T,
            num_blocks=1 + 2 * cache_T // 4)     # same token budget
        _assert_same_results(slab, paged)
        assert paged.steps < slab.steps          # more concurrency, fewer steps

    def test_paged_rejects_recurrent_families(self):
        cfg = get_arch("rwkv6-7b").reduced().replace(
            num_layers=2, d_model=64, d_ff=128, vocab_size=128)
        with pytest.raises(ValueError, match="slab"):
            make_cache_manager(cfg, 2, 16, backend="paged")


# ---------------------------------------------------------------------------
# Token-major page layout: insert, gather and copy round trips
# ---------------------------------------------------------------------------

class TestTokenMajorLayout:
    @pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
    def test_paged_insert_round_trips_through_gather(self, int8):
        """A prefill row lands token-major in the pages its block ids name
        (shared blocks redirected to trash stay untouched), and gathering
        each layer back through the table returns the row it came from."""
        from repro.kernels.paged_attention.ref import gather_kv, gather_pages
        cfg = _dense_cfg(kv_cache_int8=int8)
        L, KH, D, bs, N = (cfg.num_layers, cfg.num_kv_heads,
                           cfg.resolved_head_dim, 4, 9)
        T = 3 * bs
        keys = jax.random.split(jax.random.PRNGKey(7), 2)
        src = {"k": jax.random.normal(keys[0], (L, 2, T, KH, D)),
               "v": jax.random.normal(keys[1], (L, 2, T, KH, D))}
        if int8:
            src = {"k": (src["k"] * 40).astype(jnp.int8),
                   "k_scale": jnp.abs(src["k"][..., 0]) + 0.5,
                   "v": (src["v"] * 40).astype(jnp.int8),
                   "v_scale": jnp.abs(src["v"][..., 0]) + 0.5}
        pages = api.zeros_paged_cache(cfg, N, bs)
        assert pages["k"].shape == (L, N, bs, KH * D)
        if int8:
            assert pages["k_scale"].shape == (L, N, bs, KH)
        ids = jnp.asarray([5, 2, 7], jnp.int32)
        pages = api.paged_insert(cfg, pages, src, ids, src_index=1)
        table = ids[None]
        for layer in range(L):
            for name in ("k", "v"):
                np.testing.assert_array_equal(
                    np.asarray(gather_kv(pages[name], table, layer, KH))[0],
                    np.asarray(src[name][layer, 1]).astype(
                        pages[name].dtype))
            if int8:
                np.testing.assert_array_equal(
                    np.asarray(gather_pages(pages["k_scale"], table,
                                            layer))[0],
                    np.asarray(src["k_scale"][layer, 1]))
        untouched = [b for b in range(N) if b not in (5, 2, 7)]
        for leaf in jax.tree.leaves(pages):
            assert not np.asarray(leaf[:, untouched]).any()

    def test_copy_block_copies_every_layer(self):
        """Copy-on-write duplicates one physical page across every layer
        and leaf, and nothing else."""
        from repro.serving import make_executor
        cfg = _dense_cfg()
        ex = make_executor(cfg)
        pages = ex.zeros_paged_cache(6, 4)
        pages = jax.tree.map(
            lambda p: jax.random.normal(jax.random.PRNGKey(3), p.shape,
                                        jnp.float32).astype(p.dtype), pages)
        before = jax.tree.map(np.asarray, pages)
        after = jax.tree.map(np.asarray, ex.copy_block(pages, 4, 1))
        for name in before:
            want = before[name].copy()
            want[:, 4] = before[name][:, 1]
            np.testing.assert_array_equal(after[name], want)


# ---------------------------------------------------------------------------
# Power-of-two prefill bucketing (scheduler satellite)
# ---------------------------------------------------------------------------

class TestPow2Bucketing:
    def test_bucket_lengths(self):
        assert [prefill_bucket_len(L) for L in (1, 2, 3, 5, 8, 9, 17)] == \
            [1, 2, 4, 8, 8, 16, 32]
        assert prefill_bucket_len(9, cache_T=12) == 12   # clamped

    def test_hetero_lengths_fuse_into_one_prefill_sync(self):
        """Prompts of length 5/6/7/8 land in one pow2 bucket (8): one
        prefill group; exact bucketing needs four."""
        cfg = _dense_cfg()

        def n_groups(bucketing):
            cm = make_cache_manager(cfg, 4, 24, backend="slab")
            rq = RequestQueue()
            sched = QuasiSyncScheduler(rq, cm, SchedulerConfig(
                prefill_bucketing=bucketing))
            for L in (5, 6, 7, 8):
                rq.submit(Request(prompt=np.arange(2, 2 + L), max_new_tokens=2))
            return len(sched.plan_admissions())

        assert n_groups("pow2") == 1
        assert n_groups("exact") == 4

    def test_bucketed_outputs_identical_to_exact(self):
        cfg = _dense_cfg()
        lens = [5, 6, 7, 3]
        prompts = [_prompts(cfg, 1, L, seed=40 + L)[0] for L in lens]

        def run(bucketing):
            eng = _engine(cfg, "slab", max_new=5)
            reqs = [Request(prompt=prompts[i], max_new_tokens=5,
                            arrival_time=0.0) for i in range(4)]
            return eng.serve(reqs, n_slots=4, sched_cfg=SchedulerConfig(
                prefill_bucketing=bucketing))

        _assert_same_results(run("exact"), run("pow2"))

    def test_bucketing_reduces_syncs_on_hetero_burst(self):
        cfg = _dense_cfg()
        lens = [5, 6, 7, 8]
        prompts = [_prompts(cfg, 1, L, seed=50 + L)[0] for L in lens]

        def run(bucketing):
            eng = _engine(cfg, "slab", max_new=4)
            reqs = [Request(prompt=prompts[i], max_new_tokens=4,
                            arrival_time=0.0) for i in range(4)]
            return eng.serve(reqs, n_slots=2, sched_cfg=SchedulerConfig(
                prefill_bucketing=bucketing, lead_window=0,
                max_prefill_batch=4))

        # same token streams, same number of *syncs* is allowed to shrink;
        # outputs must agree either way
        _assert_same_results(run("exact"), run("pow2"))

    def test_recurrent_families_default_to_exact(self):
        cfg = get_arch("rwkv6-7b").reduced().replace(
            num_layers=2, d_model=64, d_ff=128, vocab_size=128)
        params = api.init(jax.random.PRNGKey(0), cfg)
        engine = ServingEngine(cfg, params, ServeConfig(max_new_tokens=3))
        prompts = [_prompts(cfg, 1, L, seed=60 + L)[0] for L in (3, 5)]
        report = engine.serve(
            [Request(prompt=prompts[i], max_new_tokens=3, arrival_time=0.0)
             for i in range(2)], n_slots=2)
        # per-request solo decode must match (right padding would break this)
        for i, r in enumerate(sorted(report.results,
                                     key=lambda r: r.request_id)):
            solo = engine.generate({"tokens": jnp.asarray(prompts[i][None])},
                                   max_new_tokens=3)
            np.testing.assert_array_equal(r.tokens, np.asarray(solo.tokens[0]))

    def test_ragged_prefill_rejected_for_recurrent(self):
        cfg = get_arch("rwkv6-7b").reduced().replace(
            num_layers=2, d_model=64, d_ff=128, vocab_size=128)
        params = api.init(jax.random.PRNGKey(0), cfg)
        batch = {"tokens": np.zeros((2, 8), np.int32)}
        with pytest.raises(ValueError, match="recurrent"):
            api.prefill(params, cfg, batch, 16,
                        prompt_lens=jnp.asarray([4, 8]))
