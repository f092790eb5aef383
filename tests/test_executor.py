"""Execution layer: the engine/executor split, the cache-donation contract
(per-step KV updates and admissions return the cache in the donated pool
buffer, not in a second cache-sized output), and the logical-axis ->
PartitionSpec helpers the mesh executor places params/caches with."""

import inspect

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import get_arch
from repro.distributed import sharding as shd
from repro.models import api
from repro.serving import (Request, ServeConfig, ServingEngine,
                           SingleDeviceExecutor, make_cache_manager,
                           make_executor, make_serving_mesh)

jax.config.update("jax_default_matmul_precision", "float32")


def _dense_cfg(**kw):
    return get_arch("qwen2-1.5b").reduced().replace(
        num_layers=2, d_model=64, d_ff=128, vocab_size=128, head_dim=16, **kw)


def _engine(cfg, **serve_kw):
    params = api.init(jax.random.PRNGKey(0), cfg)
    return ServingEngine(cfg, params, ServeConfig(**serve_kw))


def _cache_bytes(cache):
    return sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(cache))


# ---------------------------------------------------------------------------
# The refactor boundary: the engine holds NO device-shaped code
# ---------------------------------------------------------------------------

def test_engine_module_contains_no_jit_or_placement_calls():
    """The acceptance bar of the executor split: every jit trace, backend
    scope, and device placement is routed through the executor interface."""
    import repro.serving.engine as engine_mod
    src = inspect.getsource(engine_mod)
    for forbidden in ("jax.jit", "device_put", "use_matmul_backend",
                      "donate_argnums", "NamedSharding"):
        assert forbidden not in src, (
            f"serving/engine.py must not call {forbidden} directly — "
            f"that belongs to serving/executor.py")


def test_engine_exposes_executor_and_params():
    cfg = _dense_cfg(matmul_mode="bp_exact")
    engine = _engine(cfg, max_new_tokens=4)
    assert isinstance(engine.executor, SingleDeviceExecutor)
    assert engine.executor.mesh is None
    # params are the executor's placed (pre-quantized) params
    assert engine.params is engine.executor.params


def test_executor_without_params_rejects_model_entry_points():
    cfg = _dense_cfg()
    ex = make_executor(cfg)   # cache-only (what cache managers build)
    with pytest.raises(ValueError, match="without params"):
        ex.prefill({"tokens": jnp.zeros((1, 4), jnp.int32)}, 8)
    # cache ops still work
    cache = ex.zeros_cache(2, 8)
    assert jax.tree.leaves(cache)[0].shape[1] == 2


def test_make_serving_mesh_rejects_oversized_shape():
    need = len(jax.devices()) + 1
    with pytest.raises(ValueError, match="devices"):
        make_serving_mesh((1, need))


# ---------------------------------------------------------------------------
# Donation: the decode step must not allocate a second cache-sized buffer
# ---------------------------------------------------------------------------

class TestCacheDonation:
    @staticmethod
    def _step_args(engine, n_slots=2, cache_T=8):
        cache = engine.executor.zeros_cache(n_slots, cache_T)
        step = {"tokens": jnp.zeros((n_slots, 1), jnp.int32),
                "cache_len": jnp.zeros((n_slots,), jnp.int32)}
        keys = jnp.zeros((n_slots, 2), jnp.uint32)
        counts = jnp.zeros((n_slots,), jnp.uint32)
        return cache, step, keys, counts

    def test_decode_step_aliases_cache_in_hlo(self):
        """Regression: the jitted decode step's HLO must alias every cache
        leaf input to an output (tf.aliasing_output), i.e. the returned
        cache takes the donated buffer instead of a second cache-sized
        output.  Aliasing says nothing of the copies a step makes inside:
        the paged step's in-place pool update is pinned by the compile
        test for the chip (``tests/test_tpu_compile.py``)."""
        engine = _engine(_dense_cfg(), max_new_tokens=4)
        # cache big enough that activations/temps cannot mask a stray copy
        cache, step, keys, counts = self._step_args(engine, n_slots=4,
                                                    cache_T=64)
        fn = engine.executor.decode_sample_fn(0.0)
        lowered = fn.lower(cache, step, keys, counts)
        n_aliased = lowered.as_text().count("tf.aliasing_output")
        assert n_aliased >= len(jax.tree.leaves(cache)), (
            f"only {n_aliased} aliased args for "
            f"{len(jax.tree.leaves(cache))} cache leaves")
        # the whole cache rides in aliased (donated) output bytes; what the
        # step actually allocates for outputs beyond the aliased buffer is
        # just the sampled tokens — far below one cache copy.  (temp_size is
        # NOT asserted: decode attention upcasts the bf16 cache to f32 in
        # scratch, which legitimately exceeds cache bytes.)
        ma = lowered.compile().memory_analysis()
        if ma is not None and hasattr(ma, "alias_size_in_bytes"):
            assert ma.alias_size_in_bytes >= _cache_bytes(cache)
            fresh_out = ma.output_size_in_bytes - ma.alias_size_in_bytes
            assert fresh_out < _cache_bytes(cache)

    def test_decode_step_consumes_cache_buffer(self):
        engine = _engine(_dense_cfg(), max_new_tokens=4)
        cache, step, keys, counts = self._step_args(engine)
        fn = engine.executor.decode_sample_fn(0.0)
        leaves = jax.tree.leaves(cache)
        _, new_cache = fn(cache, step, keys, counts)
        assert all(l.is_deleted() for l in leaves), (
            "decode step did not donate the cache buffer")
        assert not any(l.is_deleted() for l in jax.tree.leaves(new_cache))

    def test_decode_scan_consumes_cache_buffer(self):
        engine = _engine(_dense_cfg(), max_new_tokens=4)
        B, cache_T = 2, 8
        logits, cache = engine.executor.prefill(
            {"tokens": jnp.zeros((B, 3), jnp.int32)}, cache_T)
        scan = engine.executor.decode_scan_fn(2, 0.0, None)
        leaves = jax.tree.leaves(cache)
        tok = jnp.zeros((B,), jnp.int32)
        done = jnp.zeros((B,), bool)
        out = scan(tok, cache, done, jax.random.PRNGKey(0),
                   jnp.int32(3), jnp.int32(0))
        assert all(l.is_deleted() for l in leaves)
        assert out[4].shape == (2, B)   # (chunk, B) sampled tokens

    def test_slot_insert_consumes_pool_buffer(self):
        cfg = _dense_cfg()
        engine = _engine(cfg, max_new_tokens=4)
        cm = make_cache_manager(cfg, 2, 8, executor=engine.executor)
        _, src = engine.executor.prefill(
            {"tokens": jnp.zeros((1, 4), jnp.int32)}, 8)
        slot = cm.alloc()
        pool_leaves = jax.tree.leaves(cm.cache)
        cm.insert(slot, src, length=4)
        assert all(l.is_deleted() for l in pool_leaves), (
            "slot_insert did not donate the pool buffer")
        # the prefill source survives: a group inserts it into several slots
        assert not any(l.is_deleted() for l in jax.tree.leaves(src))

    def test_paged_insert_and_copy_block_consume_pages(self):
        cfg = _dense_cfg()
        engine = _engine(cfg, max_new_tokens=4)
        cm = make_cache_manager(cfg, 2, 16, backend="paged", block_size=4,
                                executor=engine.executor)
        _, src = engine.executor.prefill(
            {"tokens": jnp.asarray([[3, 4, 5, 6]], jnp.int32)}, cm.prefill_T)
        slot = cm.alloc()
        pages_before = jax.tree.leaves(cm.pages)
        cm.insert(slot, src, length=4, tokens=[3, 4, 5, 6])
        assert all(l.is_deleted() for l in pages_before)
        pages_before = jax.tree.leaves(cm.pages)
        cm.pages = cm.executor.copy_block(cm.pages, 2, 1)
        assert all(l.is_deleted() for l in pages_before)

    def test_serve_runs_with_donation_end_to_end(self):
        # the whole continuous path over the donating executor ops stays
        # token-identical to the static path (donation is semantics-free)
        cfg = _dense_cfg()
        engine = _engine(cfg, max_new_tokens=6)
        prompts = np.asarray(jax.random.randint(
            jax.random.PRNGKey(1), (3, 5), 2, cfg.vocab_size), np.int32)
        reqs = [Request(prompt=prompts[i], max_new_tokens=6,
                        arrival_time=float(i)) for i in range(3)]
        report = engine.serve(reqs, n_slots=2)
        static = engine.generate({"tokens": jnp.asarray(prompts)},
                                 max_new_tokens=6)
        for i, r in enumerate(sorted(report.results,
                                     key=lambda r: r.request_id)):
            np.testing.assert_array_equal(r.tokens, np.asarray(static.tokens[i]))


# ---------------------------------------------------------------------------
# Logical-axis -> PartitionSpec helpers (mesh placement without a mesh)
# ---------------------------------------------------------------------------

class TestPartitionSpecHelpers:
    MESH = {"data": 2, "model": 4}

    def test_cache_pspecs_dense_decode_recipe(self):
        cfg = _dense_cfg()
        specs = api.cache_pspecs(cfg, 8, 24, self.MESH)
        # KV (L, slots, T, KH, hd): slots over "data", cache seq over "model"
        assert specs["k"] == P(None, "data", "model", None, None)
        assert specs["v"] == P(None, "data", "model", None, None)

    def test_cache_pspecs_drop_non_divisible_axes(self):
        cfg = _dense_cfg()
        specs = api.cache_pspecs(cfg, 3, 25, self.MESH)   # 3 % 2, 25 % 4
        assert specs["k"] == P(None, None, None, None, None)

    def test_cache_pspecs_int8_kv_scales(self):
        cfg = _dense_cfg(kv_cache_int8=True)
        specs = api.cache_pspecs(cfg, 8, 24, self.MESH)
        assert specs["k_scale"] == P(None, "data", "model", None)
        assert specs["v_scale"] == P(None, "data", "model", None)

    def test_cache_pspecs_recurrent_families(self):
        cfg = get_arch("rwkv6-7b").reduced().replace(
            num_layers=2, d_model=64, d_ff=128, vocab_size=128)
        specs = api.cache_pspecs(cfg, 8, 16, self.MESH)
        assert specs["x_tm"] == P(None, "data", None)
        # wkv heads axis maps to "heads" -> unsharded under decode
        assert specs["wkv"][1] == "data"

    def test_paged_cache_pspecs_fully_replicated(self):
        cfg = _dense_cfg()
        specs = api.paged_cache_pspecs(cfg, 8, 4, self.MESH)
        for s in jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)):
            assert s == P(*([None] * len(s)))

    def test_param_pspecs_tp_over_model(self):
        cfg = _dense_cfg()
        params = api.init(jax.random.PRNGKey(0), cfg)
        specs = api.param_pspecs(params, self.MESH)
        leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        # serve recipe: at least the big 2D+ kernels TP-shard their last dim
        assert any(s and s[-1] == "model" for s in leaves if len(s))
        # and nothing uses "data" on a second-to-last dim (train-only FSDP)
        assert not any(len(s) >= 2 and s[-2] == "data" for s in leaves)

    def test_logical_pspec_matches_shard_resolution_rules(self):
        # kv_seq under decode -> "model"; under train -> gathered (None)
        assert shd.logical_pspec((4, 24, 2, 16), ("batch", "kv_seq", None,
                                                  None), "decode",
                                 self.MESH) == P("data", "model", None, None)
        assert shd.logical_pspec((4, 24, 2, 16), ("batch", "kv_seq", None,
                                                  None), "train",
                                 self.MESH)[1] is None
