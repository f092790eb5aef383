"""The main path's Pallas kernels compile for a TPU v5e at qwen2-1.5b widths.

Each test compiles one kernel for a described (not attached) ``v5e:2x2``
chip and asserts the compiled program holds the Mosaic kernel
(``tpu_custom_call``).  Interpret-mode tests cannot see what the chip's
compiler refuses (block tiling, VMEM use); these can, at no chip time.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.  The persistent compilation cache is off around the
compiles (an entry written for a described chip cannot be read back).
"""

import os
import re

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import bp_matmul as bp_dispatch
from repro.kernels.bitparticle_matmul.ops import bp_matmul
from repro.kernels.paged_attention.kernel import paged_attention_kernel


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("approx", [False, True], ids=["exact", "approx"])
@pytest.mark.parametrize("m,k,n", [(8, 1536, 256), (512, 8960, 1536)],
                         ids=["decode_kv_proj", "prefill_ffn_down"])
def test_bp_matmul_compiles_for_v5e(one_chip, approx, m, k, n):
    text = _compiled_text(
        lambda a, w, sa, sw: bp_matmul(a, w, sa, sw, approx=approx),
        one_chip, ((m, k), jnp.int8), ((k, n), jnp.int8),
        ((m, 1), jnp.float32), ((n,), jnp.float32))
    assert "tpu_custom_call" in text
    assert f"bitparticle_matmul_{'approx' if approx else 'exact'}" in text


@pytest.mark.parametrize("approx", [False, True], ids=["exact", "approx"])
def test_bp_matmul_compiles_under_raised_default_precision(one_chip, approx):
    """A process-wide ``jax_default_matmul_precision`` (tests and users set
    it for f32 references) must not reach the kernel's int8 contraction:
    the chip's compiler refuses an int8 matmul at a raised precision."""
    with jax.default_matmul_precision("float32"):
        text = _compiled_text(
            lambda a, w, sa, sw: bp_matmul(a, w, sa, sw, approx=approx),
            one_chip, ((8, 1536), jnp.int8), ((1536, 1536), jnp.int8),
            ((8, 1), jnp.float32), ((1536,), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("return_state", [False, True],
                         ids=["normalized", "split_kv_state"])
def test_paged_attention_compiles_for_v5e(one_chip, return_state):
    L, B, KH, G, D, bs, pages = 3, 8, 2, 6, 128, 16, 32
    n_blocks = B * pages + 1
    text = _compiled_text(
        lambda *a: paged_attention_kernel(*a, return_state=return_state),
        one_chip, ((B, KH * G, D), jnp.bfloat16),
        ((L, n_blocks, bs, KH * D), jnp.bfloat16),
        ((L, n_blocks, bs, KH * D), jnp.bfloat16),
        ((B, pages), jnp.int32), ((B,), jnp.int32), ((), jnp.int32))
    assert "tpu_custom_call" in text
    assert "paged_attention_decode" in text


def _pool_sized(text, n_blocks):
    """The compiled instructions that copy or slice a pool-shaped array:
    ``copy``/``dynamic-slice`` ops, or fusions named after them, whose
    result has the pool's block count as a dimension."""
    hits = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?(\S+) = \w+\[([\d,]*)\]\S* (\S+?)\(",
                     line)
        if not m or n_blocks not in [int(d) for d in m[2].split(",") if d]:
            continue
        name, op = m[1], m[3]
        if op in ("copy", "copy-start", "copy-done", "dynamic-slice") or (
                op == "fusion" and re.search(r"copy|dynamic-slice", name)):
            hits.append(line.strip()[:160])
    return hits


def test_paged_decode_step_keeps_the_pool_in_place(one_chip):
    """The paged decode step at qwen2-1.5b widths (d 1536, GQA 12/2, head
    dim 128, block 16; two layers, a 4000-block pool, 20 slots) holds no
    pool-sized temporary and makes no pool-shaped copy or slice: new K/V
    rows are written into the pool the layer scan carries and the kernel
    reads its layer where it lies.  Scanning the pool per layer as scan
    inputs and outputs held 263 MB of temporaries here, twice the 131 MB
    pool, and 16 pool-shaped copies and slices."""
    from repro.configs.base import get_arch
    from repro.models import api
    from repro.models.layers import quantize_dense_params
    L, n_blocks, bs, B, P = 2, 4000, 16, 20, 161
    cfg = get_arch("qwen2-1.5b").replace(num_layers=L, matmul_mode="bp_exact")
    assert (cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.resolved_head_dim) == (1536, 12, 2, 128)
    on_chip = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree)
    params = on_chip(quantize_dense_params(jax.eval_shape(
        lambda k: api.init(k, cfg), jax.random.PRNGKey(0))))
    pool = on_chip(api.paged_cache_specs(cfg, n_blocks, bs))
    step = on_chip({"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32),
                    "cache_len": jax.ShapeDtypeStruct((B,), jnp.int32),
                    "block_tables": jax.ShapeDtypeStruct((B, P), jnp.int32)})

    def decode_step(p, cache, step):
        logits, cache = api.decode_step_paged(p, cfg, dict(step, cache=cache))
        return jnp.argmax(logits, axis=-1), cache

    with bp_dispatch.use_matmul_backend("kernel"):
        compiled = jax.jit(decode_step, donate_argnums=(1,)).lower(
            params, pool, step).compile()
    text = compiled.as_text()
    assert "paged_attention_decode" in text
    assert _pool_sized(text, n_blocks) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


def test_weight_bit_sparsity_reduction_fits_beside_the_pool(one_chip):
    """The run-end deployment estimate reduces each weight slice in one
    compiled program.  At qwen2-7b's ``w_gate`` slice (3584 x 18944 int8,
    68 MB) its temporaries stay far below one int32 copy of the slice
    (272 MB), which the eager reduction built several of at once."""
    from repro.core.sparsity import bit_sparsity_fused
    arg = jax.ShapeDtypeStruct((3584, 18944), jnp.int8, sharding=one_chip)
    mem = bit_sparsity_fused.lower(arg).compile().memory_analysis()
    assert mem.temp_size_in_bytes < 64 * 2**20
