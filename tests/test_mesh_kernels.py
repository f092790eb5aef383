"""shard_map kernel parity under the mesh: the Pallas fast path no longer
falls back to the XLA oracle when a mesh is active.

Covers the regression (``resolve_matmul_backend("kernel")`` stays "kernel"
under an active mesh), the one-time fallback ledger, interpret-mode kernel
vs XLA-oracle parity for both kernels on ragged shapes under the 8-virtual-
CPU mesh (all three matmul partition strategies, S=1 decode rows and
S=K+1 verify rows, split-KV and replicated paged attention), and serve-level
token identity of the mesh-kernel path against single-device-kernel and
mesh-XLA.  Multi-device cases run in subprocesses (XLA_FLAGS must be set
before jax initializes — the ``tests/test_sharded_serving.py`` pattern).
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(script: str):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(SRC)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, timeout=600, env=env)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


def test_resolve_backend_keeps_kernel_under_mesh():
    """Regression for the blanket mesh downgrade: kernel backends resolve
    to themselves under an active mesh (the dispatch sites shard_map the
    kernels instead)."""
    import jax
    from jax.sharding import Mesh
    from repro.core import bp_matmul as bpm
    from repro.distributed import sharding as shd

    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    with jax.set_mesh(mesh):
        assert shd.current_mesh() is not None
        assert bpm.resolve_matmul_backend("kernel") == "kernel"
        assert bpm.resolve_matmul_backend("kernel_interpret") == \
            "kernel_interpret"
        assert bpm.resolve_matmul_backend("xla") == "xla"
    assert shd.current_mesh() is None


def test_backend_fallback_ledger_counts_and_paged_scale_demotion():
    """Remaining per-call kernel->xla demotions are never silent: the int8
    KV scale-page path records itself in the fallback ledger (once per
    reason in the log, every occurrence in the count)."""
    import jax
    import jax.numpy as jnp
    from repro.core import bp_matmul as bpm
    from repro.kernels.paged_attention.ops import paged_attention
    from repro.kernels.paged_attention.ref import paged_attention_xla

    bpm.clear_backend_fallbacks()
    try:
        rng = np.random.default_rng(0)
        B, H, KH, D, bs, P = 2, 2, 1, 8, 4, 2
        N = 5
        q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
        kp = jnp.asarray(rng.normal(size=(1, N, bs, KH * D)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(1, N, bs, KH * D)), jnp.float32)
        ks = jnp.ones((1, N, bs, KH), jnp.float32)
        bt = jnp.asarray(rng.integers(1, N, size=(B, P)), jnp.int32)
        ln = jnp.asarray(rng.integers(0, P * bs, size=(B,)), jnp.int32)

        out = paged_attention(q, kp, vp, bt, ln, k_scale_pages=ks,
                              v_scale_pages=ks, backend="kernel_interpret")
        ref = paged_attention_xla(q, kp, vp, bt, ln, k_scale_pages=ks,
                                  v_scale_pages=ks)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)
        ledger = bpm.backend_fallbacks()
        assert len(ledger) == 1 and list(ledger.values()) == [1]
        paged_attention(q, kp, vp, bt, ln, k_scale_pages=ks,
                        backend="kernel_interpret")
        assert list(bpm.backend_fallbacks().values()) == [2]
        # an explicit xla request is not a fallback
        paged_attention(q, kp, vp, bt, ln, backend="xla")
        assert list(bpm.backend_fallbacks().values()) == [2]
    finally:
        bpm.clear_backend_fallbacks()


_HEADER = """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax, jax.numpy as jnp
    jax.config.update("jax_default_matmul_precision", "float32")
    from jax.sharding import Mesh
    from repro.distributed import sharding as shd
    from repro.core import bp_matmul as bpm

    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                ("data", "model"))
"""


@pytest.mark.slow
def test_mesh_matmul_kernel_parity_all_strategies():
    """quantized_matmul under the 2x4 mesh, kernel_interpret vs the XLA
    oracle, for every partition strategy (column split / split-K / fully
    replicated), both quant modes, S=1 decode rows and S=4 verify rows.
    The sharded kernel wrapper itself is additionally pinned bit-identical
    to the single-device kernel on fixed int8 operands."""
    _run(_HEADER + """
    from repro.core import quant
    from repro.core.bp_matmul import quantized_matmul
    from repro.kernels.bitparticle_matmul.ops import (
        _matmul_strategy, bp_matmul, bp_matmul_sharded)

    axes = shd.mesh_axes_dict(mesh)
    # (B, S, K, N) -> expected strategy on ("data"=2, "model"=4)
    cases = [
        ((4, 1, 33, 128), "col"),      # N % 4 == 0: column split
        ((4, 4, 33, 128), "col"),      # S=4: speculative verify rows
        ((4, 1, 128, 130), "splitk"),  # K % 4 == 0, N ragged: split-K psum
        ((4, 4, 128, 130), "splitk"),
        ((5, 1, 33, 17), "rep"),       # nothing divides: replicated
    ]
    for (b, s, k, n), want in cases:
        got_strat = _matmul_strategy([b, s], k, n, axes)[1]
        assert got_strat == want, ((b, s, k, n), got_strat, want)
        x = jax.random.normal(jax.random.PRNGKey(b + n), (b, s, k),
                              jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.float32)
        for mode in ("bp_exact", "bp_approx"):
            def f(x, w):
                w_q, w_s = quant.quantize_per_channel(w, channel_axis=-1)
                return quantized_matmul(x, w_q, w_s.reshape(-1), mode)
            with jax.set_mesh(mesh), bpm.use_matmul_backend("xla"):
                ref = jax.jit(f)(x, w)
            with jax.set_mesh(mesh), \\
                 bpm.use_matmul_backend("kernel_interpret"):
                got = jax.jit(f)(x, w)
            np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
            print("OK", (b, s, k, n), want, mode)

    # the shard_map wrapper is bit-identical to the unsharded kernel when
    # quantized operands and scales are fixed (integer partials + identical
    # dequant epilogue ordering)
    rng = np.random.default_rng(0)
    for (b, s, k, n), want in cases:
        xq = jnp.asarray(rng.integers(-127, 128, size=(b, s, k)), jnp.int8)
        wq = jnp.asarray(rng.integers(-127, 128, size=(k, n)), jnp.int8)
        sa = jnp.asarray(rng.random((b, s, 1)), jnp.float32)
        sw = jnp.asarray(rng.random((n,)), jnp.float32)
        for approx in (False, True):
            single = bp_matmul(xq, wq, sa, sw, approx=approx, interpret=True)
            with jax.set_mesh(mesh):
                sharded = jax.jit(lambda *a: bp_matmul_sharded(
                    *a, approx=approx, interpret=True, mesh=mesh))(
                    xq, wq, sa, sw)
            np.testing.assert_array_equal(np.asarray(single),
                                          np.asarray(sharded))
    print("BITWISE OK")
""")


@pytest.mark.slow
def test_mesh_paged_attention_kernel_parity():
    """Paged-attention kernel under the 2x4 mesh vs the XLA gather oracle:
    the split-KV path (page dim divisible by "model" -> per-shard online
    softmax + (m, l, acc) cross-shard combine) and the replicated path
    (ragged page count), ragged lengths including length 0, each reading
    one layer of a stacked three-layer pool through the layer operand."""
    _run(_HEADER + """
    from repro.kernels.paged_attention.ops import paged_attention
    from repro.kernels.paged_attention.ref import paged_attention_xla

    rng = np.random.default_rng(0)
    L = 3
    #          B  H  KH  D  bs  P  layer (P % 4 == 0 -> KV split)
    cases = [(4, 4, 2, 16, 4, 8, 2),
             (4, 8, 4, 16, 2, 12, 1),
             (4, 4, 2, 16, 4, 5, 1),    # ragged page count: replicated
             (6, 2, 2,  8, 4, 4, 0)]    # B % 2 == 0 but B % 4 != 0
    for (B, H, KH, D, bs, P, layer) in cases:
        N = P * B + 1
        q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
        kp = jnp.asarray(rng.normal(size=(L, N, bs, KH * D)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(L, N, bs, KH * D)), jnp.float32)
        bt = jnp.asarray(rng.integers(1, N, size=(B, P)), jnp.int32)
        ln = np.asarray(rng.integers(0, P * bs, size=(B,)), np.int32)
        ln[0] = 0                      # only the just-written token valid
        ln = jnp.asarray(ln)
        ref = paged_attention_xla(q, kp, vp, bt, ln, layer)
        with jax.set_mesh(mesh), \\
             bpm.use_matmul_backend("kernel_interpret"):
            got = jax.jit(paged_attention)(q, kp, vp, bt, ln,
                                           jnp.int32(layer))
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
        print("OK", (B, H, KH, D, bs, P, layer))
""")


@pytest.mark.slow
@pytest.mark.parametrize("cache_backend", ["slab", "paged"])
def test_mesh_kernel_serve_token_identity(cache_backend):
    """The acceptance bar: mesh serve under ``matmul_backend=
    "kernel_interpret"`` is token-identical to single-device-kernel AND
    mesh-XLA serve (2x4 mesh, plain and speculative decoding)."""
    _run(_HEADER + """
    from repro.configs.base import get_arch
    from repro.models import api
    from repro.serving import (MeshExecutor, Request, SchedulerConfig,
                               ServeConfig, ServingEngine)

    base = get_arch("qwen2-1.5b").reduced().replace(
        num_layers=2, d_model=64, d_ff=128, vocab_size=128, head_dim=16,
        matmul_mode="bp_exact")
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (4, 6), 2, base.vocab_size), np.int32)

    def tokens(mesh_shape, mm, spec=False):
        cfg = base.replace(matmul_backend=mm)
        params = api.init(jax.random.PRNGKey(0), cfg)
        sc = dict(max_new_tokens=8, temperature=0.0,
                  cache_backend=%(backend)r, block_size=4,
                  mesh_shape=mesh_shape)
        kw = {}
        if spec:
            sc.update(draft="model", num_draft_tokens=3)
            kw = dict(draft_cfg=cfg, draft_params=params)
        engine = ServingEngine(cfg, params, ServeConfig(**sc), **kw)
        if mesh_shape is not None:
            assert isinstance(engine.executor, MeshExecutor)
        assert engine.executor.matmul_backend == mm
        reqs = [Request(prompt=prompts[i], max_new_tokens=[8, 3, 6, 8][i],
                        arrival_time=float(i)) for i in range(4)]
        rep = engine.serve(reqs, n_slots=2,
                           sched_cfg=SchedulerConfig(lead_window=2))
        if spec:
            assert rep.acceptance_rate > 0.0
        return [list(r.tokens) for r in
                sorted(rep.results, key=lambda r: r.request_id)]

    single_kernel = tokens(None, "kernel_interpret")
    assert tokens((2, 4), "kernel_interpret") == single_kernel
    assert tokens((2, 4), "xla") == single_kernel
    assert tokens((2, 4), "kernel_interpret", spec=True) == single_kernel
    print("OK serve", %(backend)r)
""" % {"backend": cache_backend})
