"""Serving execution layer: device placement, jit tracing, donation, meshes.

The engine (``serving/engine.py``) is pure host-side orchestration —
admission, scheduling, preemption, token bookkeeping.  Everything
device-shaped lives here, behind one ``Executor`` interface:

  * **jit tracing** — every compiled entry point (prefill, fused
    decode+sample step, multi-token decode scan, cache surgery) is traced
    under the config's ``matmul_backend`` (``core.bp_matmul`` dispatch), so
    ``bp_*`` contractions route through the fused Pallas kernel / XLA
    oracle exactly as before the engine/executor split.
  * **buffer donation** — the pooled decode cache is donated
    (``donate_argnums``) into the decode step, the decode scan chunk, and
    the ``slot_insert``/``paged_insert``/``copy_block`` surgery ops, so
    each returns its cache in the input's buffer rather than in a second
    cache-sized output (``tests/test_executor.py`` pins the aliasing in the
    HLO).  Donation alone does not keep a step from copying the cache
    inside: the block-paged decode step also carries its pool through the
    layer scan and writes each new K/V row in place, which is what keeps a
    pool-sized temporary and the per-layer copies out of the program
    (``tests/test_tpu_compile.py`` compiles it for the chip to pin that).
  * **device placement** — params are placed once at construction; caches
    are allocated through the executor so their residency/sharding is an
    executor decision, not an engine one.

Two executors ship behind the interface:

  * :class:`SingleDeviceExecutor` — the default: plain jit on the default
    device (the pre-split behavior).
  * :class:`MeshExecutor` — tensor-parallel serving over a
    ``("data", "model")`` jax mesh.  Pre-quantized weights are TP-sharded
    over ``"model"`` (``distributed.sharding.param_specs``, serve recipe:
    last dim of every dense kernel), the slab KV cache is sharded per the
    existing ``decode`` logical-axis recipe (slot/batch axis over
    ``"data"``, KV sequence axis over ``"model"`` — split-KV decode), and
    the block-paged cache + block tables stay replicated
    (``api.paged_cache_logical_axes``).  Every trace runs inside the mesh +
    ``decode`` recipe scope, so the model's ``shard()`` constraints engage.
    Kernel backends stay active under the mesh: the dispatch sites wrap the
    Pallas kernels in ``shard_map`` (TP column / split-K matmul partitions,
    split-KV paged attention with an (m, l, acc) cross-shard softmax
    combine — ``kernels/*/ops.py``), so ``matmul_backend="kernel"`` means
    the kernel on every executor.  Greedy outputs are token-identical to
    single-device execution for both backends
    (``tests/test_sharded_serving.py``, ``tests/test_mesh_kernels.py``).

``params`` may be None for cache-only use: the cache managers build a
default executor when constructed directly (tests); the model entry points
then raise.
"""

from __future__ import annotations

import contextlib
import functools
import threading
from typing import Dict, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import bp_matmul
from repro.core import probe as core_probe
from repro.distributed import sharding as shd
from repro.models import api
from repro.serving.faults import StepCompileError


class Executor:
    """Execution-layer interface + the shared jit/donation machinery.

    Subclasses override the placement hooks (``_place_params``,
    ``_place_cache``, ``put``) and the trace scope (``_scopes``); the entry
    points themselves are layout-agnostic.
    """

    def __init__(self, cfg, params=None,
                 matmul_backend: Optional[str] = None):
        from repro.serving import telemetry as _telemetry
        from repro.serving import faults as _faults
        self.cfg = cfg
        self.matmul_backend = (getattr(cfg, "matmul_backend", "auto")
                               if matmul_backend is None else matmul_backend)
        # observability handle: the serve loop attaches its own via
        # ``set_telemetry`` so ``put`` transfers count against the run;
        # default is the shared no-op handle (zero overhead)
        self.telemetry = _telemetry.NULL_TELEMETRY
        # fault-injection handle, threaded exactly like telemetry; checks
        # fire BEFORE a jit dispatch so an injected fault never consumes
        # the donated cache (retry-safe by construction)
        self.faults = _faults.NULL_INJECTOR
        # sparsity-probe handle, threaded the same way; only consulted when
        # the serve loop asks for probed step-fn variants
        from repro.serving import probe as _probe
        self.probe = _probe.NULL_PROBE
        self._params = (self._place_params(params)
                        if params is not None else None)
        self._jits: Dict[tuple, object] = {}

    def set_telemetry(self, telemetry) -> None:
        """Attach a serve loop's telemetry handle (byte counters / spans);
        None reverts to the shared no-op handle."""
        from repro.serving import telemetry as _telemetry
        self.telemetry = (telemetry if telemetry is not None
                          else _telemetry.NULL_TELEMETRY)

    def set_faults(self, injector) -> None:
        """Attach a fault injector (None reverts to the no-op handle)."""
        from repro.serving import faults as _faults
        self.faults = (injector if injector is not None
                       else _faults.NULL_INJECTOR)

    def set_probe(self, probe) -> None:
        """Attach a sparsity probe (None reverts to the no-op handle)."""
        from repro.serving import probe as _probe
        self.probe = probe if probe is not None else _probe.NULL_PROBE

    def _require_probe_support(self):
        from repro.serving.probe import probe_supported
        if not probe_supported(self.cfg):
            raise ValueError(
                f"sparsity probe unsupported for family={self.cfg.family!r} "
                f"matmul_mode={self.cfg.matmul_mode!r}: the probe taps int8 "
                f"operands at the quantized-matmul boundary (causal-LM "
                f"family + bp_exact/bp_approx mode)")

    def reset(self) -> None:
        """Drop every cached jitted entry point (recovery path: after an
        executor failure the serve loop rebuilds its step functions from a
        clean trace cache and replays in-flight requests)."""
        self._jits.clear()

    def set_matmul_backend(self, backend: str) -> None:
        """Switch the matmul backend (degradation ladder: repeated kernel
        faults fall back to the XLA oracle) and invalidate every trace
        compiled under the old one."""
        self.matmul_backend = backend
        self._jits.clear()

    # -- placement hooks (single-device defaults) ---------------------------

    @property
    def mesh(self):
        """The mesh this executor runs over (None on a single device)."""
        return None

    def _place_params(self, params):
        return params

    def _place_cache(self, cache, *, paged: bool):
        return cache

    def put(self, x):
        """Host array -> device array (replicated under a mesh); the bytes
        moved count against the attached telemetry handle."""
        x = jnp.asarray(x)
        self.telemetry.count("h2d_bytes", getattr(x, "nbytes", 0))
        return x

    def _trace_scopes(self):
        """Context managers entered INSIDE the traced function — they set
        thread-local state consulted while tracing (backend dispatch,
        recipe rules), so on cached dispatches they cost nothing."""
        return [bp_matmul.use_matmul_backend(self.matmul_backend)]

    def _call_scopes(self):
        """Context managers entered around every CALL — only what cannot
        live inside a trace (mesh activation on the mesh executor).  Empty
        here, so the single-device hot loop is a bare jitted dispatch."""
        return []

    # -- jit plumbing -------------------------------------------------------

    def _jit(self, fn, **jit_kwargs):
        """jax.jit with the executor's scopes applied: trace-time scopes
        wrap the traced body (entered only while tracing), call-time scopes
        wrap the dispatch.  The returned callable keeps a ``.lower``
        (scoped the same way) so tests can inspect the compiled HLO —
        e.g. the donation/aliasing regression test.

        An exception from a call that traced the program is a trace, lower
        or compile failure and surfaces as :class:`StepCompileError`, which
        the serve loop never recovers from.  Execution is asynchronous, so
        runtime failures of an executed step surface later, where the
        caller waits for the result."""
        trace_scopes = self._trace_scopes
        # per-thread count of this program's traces: a call that moved it
        # traced the program, so it also lowered and compiled it
        traces = threading.local()

        @functools.wraps(fn)
        def traced(*args, **kwargs):
            traces.n = getattr(traces, "n", 0) + 1
            with contextlib.ExitStack() as stack:
                for ctx in trace_scopes():
                    stack.enter_context(ctx)
                return fn(*args, **kwargs)

        jitted = jax.jit(traced, **jit_kwargs)

        def call(*args, **kwargs):
            n = getattr(traces, "n", 0)
            try:
                with contextlib.ExitStack() as stack:
                    for ctx in self._call_scopes():
                        stack.enter_context(ctx)
                    return jitted(*args, **kwargs)
            except Exception as e:
                if getattr(traces, "n", 0) != n:
                    raise StepCompileError(e) from e
                raise

        def lower(*args, **kwargs):
            with contextlib.ExitStack() as stack:
                for ctx in self._call_scopes():
                    stack.enter_context(ctx)
                return jitted.lower(*args, **kwargs)

        call.lower = lower
        return call

    def _get(self, key, build):
        fn = self._jits.get(key)
        if fn is None:
            fn = build()
            self._jits[key] = fn
        return fn

    def _require_params(self):
        if self._params is None:
            raise ValueError(
                "this executor was built without params (cache-only use); "
                "model entry points are unavailable")

    # -- model entry points -------------------------------------------------

    @property
    def params(self):
        """The placed (and, upstream, pre-quantized) model params."""
        return self._params

    def prefill(self, batch, cache_T: int, prompt_lens=None,
                probed: bool = False):
        """Compiled prefill; ``prompt_lens`` selects the ragged right-padded
        variant (per-row last-position logits, pow2 prefill buckets).
        ``probed=True`` jits a separate variant whose body runs under the
        sparsity tap and additionally returns the fused
        ``(n_layers[+1], N_STATS)`` activation stats."""
        self._require_params()
        self.faults.check("prefill")
        cfg = self.cfg
        if probed:
            self._require_probe_support()

        # the jitted function's name is the program's name in a device
        # trace (``jit_prefill_step``): keep it stable
        def prefill_step(p, b, t, lens=None):
            if not probed:
                return api.prefill(p, cfg, b, t, prompt_lens=lens)
            with core_probe.probe_tap():
                logits, cache = api.prefill(p, cfg, b, t, prompt_lens=lens)
                stats = core_probe.collect()
            return logits, cache, stats

        if prompt_lens is None:
            fn = self._get(("prefill", bool(probed)), lambda: self._jit(
                prefill_step, static_argnums=(2,)))
            return fn(self._params, batch, cache_T)
        fn = self._get(("prefill_ragged", bool(probed)), lambda: self._jit(
            prefill_step, static_argnums=(2,)))
        return fn(self._params, batch, cache_T, jnp.asarray(prompt_lens))

    def decode_step(self, step):
        """One raw decode dispatch (logits leave the device; no sampling
        fusion, no donation) — the legacy-loop comparison path used by
        ``benchmarks/decode_latency.py`` and logits-level tests."""
        self._require_params()
        cfg = self.cfg
        fn = self._get(("decode_step",), lambda: self._jit(
            lambda p, s: api.decode_step(p, cfg, s)))
        return fn(self._params, step)

    def decode_sample_fn(self, temperature: float, paged: bool = False,
                         probed: bool = False):
        """``fn(cache, step, keys, counts) -> (tokens, new_cache)`` for the
        continuous path: decode + per-slot sampling fused into ONE dispatch
        (only the (n_slots,) sampled tokens cross to the host, never the
        logits), with the cache buffer DONATED — the returned cache takes
        the input's buffer (the paged step also updates it in place; see
        the module docstring).  ``paged`` routes through
        the block-table decode step (``step`` then carries
        ``block_tables``).  ``probed=True`` jits a separate tapped variant
        returning ``(tokens, new_cache, stats)`` (donation unchanged)."""
        self._require_params()
        cfg = self.cfg
        if probed:
            self._require_probe_support()

        def build():
            decode = api.decode_step_paged if paged else api.decode_step

            # named for the device trace (``jit_decode_step``)
            def decode_step(p, cache, step, keys, counts):
                step = dict(step, cache=cache)
                # optional fault-injection mask (n_slots,) bool: NaN the
                # whole logit row for flagged slots (exercises the guard)
                nan_mask = step.pop("nan_mask", None)
                with contextlib.ExitStack() as tap:
                    if probed:
                        tap.enter_context(core_probe.probe_tap())
                    logits, new_cache = decode(p, cfg, step)
                    stats = core_probe.collect() if probed else None
                if nan_mask is not None:
                    logits = jnp.where(nan_mask[:, None], jnp.nan, logits)
                # pin the output layout to the input layout so the donated
                # buffer aliases instead of resharding (no-op off-mesh)
                new_cache = api.shard_cache(cfg, new_cache, paged=paged)
                if temperature <= 0:
                    tok = jnp.argmax(logits, axis=-1)
                else:
                    ks = jax.vmap(jax.random.fold_in)(keys, counts)
                    tok = jax.vmap(jax.random.categorical)(
                        ks, logits / temperature)
                # NaN guard, fused into the step: a non-finite logit row
                # yields the -1 sentinel (argmax/categorical are always
                # >= 0) so the loop can fail ONLY the affected slot
                ok = jnp.isfinite(logits).all(axis=-1)
                tok = jnp.where(ok, tok, -1)
                if probed:
                    return tok.astype(jnp.int32), new_cache, stats
                return tok.astype(jnp.int32), new_cache

            jitted = self._jit(decode_step, donate_argnums=(1,))

            def fn(cache, step, keys, counts):
                self.faults.check("step")
                self.faults.delay()
                return jitted(self._params, cache, step, keys, counts)

            fn.lower = lambda cache, step, keys, counts: jitted.lower(
                self._params, cache, step, keys, counts)
            return fn

        return self._get(("decode_sample", float(temperature), bool(paged),
                          bool(probed)), build)

    def verify_sample_fn(self, paged: bool = False, probed: bool = False):
        """``fn(cache, step) -> (greedy (B, S) int32 tokens, new_cache)``
        for the speculative path: ONE forward pass appends the S fed tokens
        (last committed + drafts) at per-slot positions and the per-position
        greedy argmax is fused into the dispatch — only the (B, S) token
        grid crosses to the host, never (B, S, V) logits.  The cache buffer
        is donated exactly like the decode step.  Greedy-only by design:
        the accept rule compares argmax streams, which is what makes
        speculative outputs token-identical to non-speculative greedy.
        ``probed=True``: tapped variant returning (tokens, cache, stats)."""
        self._require_params()
        cfg = self.cfg
        if probed:
            self._require_probe_support()

        def build():
            verify = api.verify_step_paged if paged else api.verify_step

            # named for the device trace (``jit_verify_step``)
            def verify_step(p, cache, step):
                step = dict(step, cache=cache)
                nan_mask = step.pop("nan_mask", None)
                with contextlib.ExitStack() as tap:
                    if probed:
                        tap.enter_context(core_probe.probe_tap())
                    logits, new_cache = verify(p, cfg, step)
                    stats = core_probe.collect() if probed else None
                if nan_mask is not None:
                    logits = jnp.where(nan_mask[:, None, None], jnp.nan,
                                       logits)
                new_cache = api.shard_cache(cfg, new_cache, paged=paged)
                tok = jnp.argmax(logits, axis=-1)
                # same -1 sentinel as the decode step, per (slot, position)
                ok = jnp.isfinite(logits).all(axis=-1)
                tok = jnp.where(ok, tok, -1)
                if probed:
                    return tok.astype(jnp.int32), new_cache, stats
                return tok.astype(jnp.int32), new_cache

            jitted = self._jit(verify_step, donate_argnums=(1,))

            def fn(cache, step):
                self.faults.check("step")
                self.faults.delay()
                return jitted(self._params, cache, step)

            fn.lower = lambda cache, step: jitted.lower(self._params, cache,
                                                        step)
            return fn

        return self._get(("verify_sample", bool(paged), bool(probed)), build)

    def decode_scan_fn(self, chunk: int, temperature: float,
                       eos_id: Optional[int]):
        """``fn(tok, cache, done, key, pos0, i0) -> (tok, cache, done, key,
        tokens (chunk, B))`` for the static path: a jitted ``lax.scan`` over
        ``chunk`` decode steps with sampling + EOS masking folded in and the
        cache donated across the dispatch."""
        self._require_params()
        cfg = self.cfg

        def build():
            def scan_fn(p, tok, cache, done, key, pos0, i0):
                def body(carry, j):
                    tok, cache, done, key = carry
                    if eos_id is not None:
                        done = done | (tok == eos_id)
                    step = {"tokens": tok[:, None], "cache": cache,
                            "cache_len": (pos0 + j).astype(jnp.int32)}
                    logits, cache = api.decode_step(p, cfg, step)
                    key = jax.random.fold_in(key, i0 + j)
                    if temperature <= 0:
                        new = jnp.argmax(logits, axis=-1)
                    else:
                        new = jax.random.categorical(
                            key, logits / temperature, axis=-1)
                    new = new.astype(tok.dtype)
                    if eos_id is not None:
                        new = jnp.where(done, eos_id, new)
                    return (new, cache, done, key), new

                carry, toks = jax.lax.scan(
                    body, (tok, cache, done, key), jnp.arange(chunk))
                tok, cache, done, key = carry
                cache = api.shard_cache(cfg, cache)
                return tok, cache, done, key, toks

            jitted = self._jit(scan_fn, donate_argnums=(2,))
            return lambda tok, cache, done, key, pos0, i0: jitted(
                self._params, tok, cache, done, key, pos0, i0)

        return self._get(("decode_scan", int(chunk), float(temperature),
                          eos_id), build)

    # -- cache allocation / surgery (params-free) ---------------------------

    def zeros_cache(self, n_slots: int, cache_T: int):
        """Allocate the pooled slab decode cache, placed per this
        executor's layout."""
        return self._place_cache(api.zeros_cache(self.cfg, n_slots, cache_T),
                                 paged=False)

    def zeros_paged_cache(self, num_blocks: int, block_size: int):
        return self._place_cache(
            api.zeros_paged_cache(self.cfg, num_blocks, block_size),
            paged=True)

    def slot_insert(self, pool, src, slot: int, src_index: int = 0):
        """Install request ``src_index`` of a prefill cache into ``slot`` of
        the pooled cache; the pool buffer is donated (in-place surgery, no
        second pool-sized allocation)."""
        self.faults.check("oom")
        cfg = self.cfg
        fn = self._get(("slot_insert",), lambda: self._jit(
            lambda pool, src, slot, i: api.shard_cache(
                cfg, api.slot_insert(cfg, pool, src, slot, i)),
            donate_argnums=(0,)))
        return fn(pool, src, jnp.int32(slot), jnp.int32(src_index))

    def paged_insert(self, pages, src, block_ids, src_index: int = 0):
        """Scatter a prefill cache into physical pages through ``block_ids``
        (trash-redirected entries skip shared blocks); pages donated."""
        self.faults.check("oom")
        cfg = self.cfg
        fn = self._get(("paged_insert",), lambda: self._jit(
            lambda pages, src, ids, i: api.shard_cache(
                cfg, api.paged_insert(cfg, pages, src, ids, i), paged=True),
            donate_argnums=(0,)))
        return fn(pages, src, jnp.asarray(block_ids, jnp.int32),
                  jnp.int32(src_index))

    def copy_block(self, pages, dst: int, src: int):
        """Copy physical page ``src`` -> ``dst`` (copy-on-write); pages
        donated."""
        self.faults.check("oom")
        cfg = self.cfg
        fn = self._get(("copy_block",), lambda: self._jit(
            lambda pages, dst, src: api.shard_cache(
                cfg,
                jax.tree.map(lambda p: p.at[:, dst].set(p[:, src]), pages),
                paged=True),
            donate_argnums=(0,)))
        return fn(pages, jnp.int32(dst), jnp.int32(src))


class SingleDeviceExecutor(Executor):
    """The default executor: plain jit on the default device."""


class MeshExecutor(Executor):
    """Tensor-parallel serving executor over a ``("data", "model")`` mesh.

    Weights TP-shard over ``"model"`` (last dims, ``param_specs`` serve
    recipe), the slab cache shards per the ``decode`` logical-axis recipe
    (slots over ``"data"``, KV sequence over ``"model"``), paged pages and
    block tables replicate.  Non-divisible dims silently stay replicated —
    the same model code runs on every mesh shape.
    """

    def __init__(self, cfg, params=None, *, mesh: Mesh,
                 matmul_backend: Optional[str] = None,
                 recipe_name: str = "decode"):
        self._mesh = mesh
        self._mesh_axes = shd.mesh_axes_dict(mesh)
        self.recipe_name = recipe_name
        super().__init__(cfg, params, matmul_backend)

    @property
    def mesh(self):
        return self._mesh

    def _trace_scopes(self):
        return [shd.recipe(self.recipe_name),
                bp_matmul.use_matmul_backend(self.matmul_backend)]

    def _call_scopes(self):
        # mesh activation cannot happen inside a trace; the recipe/backend
        # thread-locals ride in the traced body (_trace_scopes)
        return [jax.set_mesh(self._mesh)]

    def _place_params(self, params):
        shardings = shd.named_shardings(params, self.recipe_name, self._mesh)
        return jax.tree.map(jax.device_put, params, shardings)

    def _place_cache(self, cache, *, paged: bool):
        specs = api.cache_pspec_tree(self.cfg, cache, self._mesh_axes,
                                     self.recipe_name, paged=paged)
        return jax.tree.map(
            lambda leaf, s: jax.device_put(
                leaf, NamedSharding(self._mesh, s)),
            cache, specs)

    def put(self, x):
        x = jnp.asarray(x)
        self.telemetry.count("h2d_bytes", getattr(x, "nbytes", 0))
        return jax.device_put(
            x, NamedSharding(self._mesh, P(*([None] * x.ndim))))


def make_serving_mesh(shape: Sequence[int]) -> Mesh:
    """A ``("data", "model")`` mesh over the first ``prod(shape)`` local
    devices — validation here, construction shared with
    ``launch.mesh.make_local_mesh`` (one version-portable mesh factory)."""
    shape = tuple(int(d) for d in shape)
    if len(shape) != 2:
        raise ValueError(f"mesh shape must be (data, model), got {shape!r}")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise ValueError(
            f"mesh shape {shape} needs {n} devices, found {len(devices)} "
            f"(on CPU, set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n} before jax initializes)")
    from repro.launch.mesh import make_local_mesh
    return make_local_mesh(*shape)


def make_executor(cfg, params=None, *, mesh: Optional[Mesh] = None,
                  mesh_shape: Optional[Tuple[int, int]] = None,
                  matmul_backend: Optional[str] = None) -> Executor:
    """Build the executor selected by ``mesh``/``mesh_shape`` (None/None ->
    single device)."""
    if mesh is None and mesh_shape is not None:
        mesh = make_serving_mesh(mesh_shape)
    if mesh is not None:
        return MeshExecutor(cfg, params, mesh=mesh,
                            matmul_backend=matmul_backend)
    return SingleDeviceExecutor(cfg, params, matmul_backend=matmul_backend)
