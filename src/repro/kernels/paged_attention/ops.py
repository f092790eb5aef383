"""Public paged-attention entry point with backend dispatch.

Routes through the same trace-time backend switch as the BitParticle matmul
(``core.bp_matmul.resolve_matmul_backend``), so the serving engine's
``use_matmul_backend`` scoping covers the attention kernel too:

  ``auto``              Pallas kernel on TPU, XLA gather elsewhere.
  ``kernel``            force the compiled Pallas kernel.
  ``kernel_interpret``  the kernel under the Pallas interpreter (CPU
                        validation — the parity oracle for tests).
  ``xla``               the dense-gather reference (:mod:`.ref`).

Under an active mesh trace the kernel runs inside ``shard_map``: the page
pool is replicated (see ``models/api.py::paged_cache_logical_axes``), so
the block-table page dim is split over "model" when it divides — each shard
runs online softmax over its local KV split and the (m, l, acc) partial
state is combined across shards (``sharding.combine_softmax_state``) —
and the batch dim over "data" when it divides.  A bare ``pallas_call``
must never trace under GSPMD (it would see one shard of its operands), so
the mesh path always wraps, even when no axis divides (replicated compute).

int8 KV scale pages always take the XLA path (the kernel gathers float
pages only); when that demotes an explicit kernel request the downgrade is
recorded once via ``bp_matmul.note_backend_fallback`` instead of happening
silently.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.bp_matmul import note_backend_fallback, resolve_matmul_backend
from repro.distributed import sharding as shd
from repro.kernels.paged_attention.kernel import paged_attention_kernel
from repro.kernels.paged_attention.ref import paged_attention_xla


def paged_attention(q, k_pages, v_pages, block_tables, lengths, layer=0, *,
                    k_scale_pages=None, v_scale_pages=None,
                    backend: str = None):
    """Paged decode attention over layer ``layer`` of the stacked pool; see
    :func:`.ref.paged_attention_xla` for the argument contract.
    ``backend`` overrides the process/trace default."""
    b = resolve_matmul_backend(backend)
    if b != "xla" and (k_scale_pages is not None or v_scale_pages is not None):
        note_backend_fallback(
            "paged_attention: int8 KV scale pages -> xla gather oracle "
            "(the kernel gathers float pages only)")
        b = "xla"
    if b == "xla":
        return paged_attention_xla(
            q, k_pages, v_pages, block_tables, lengths, layer,
            k_scale_pages=k_scale_pages, v_scale_pages=v_scale_pages)
    interpret = b == "kernel_interpret"
    mesh = shd.current_mesh()
    if mesh is not None:
        return _paged_attention_sharded(
            q, k_pages, v_pages, block_tables, lengths, layer,
            interpret=interpret, mesh=mesh)
    return paged_attention_kernel(q, k_pages, v_pages, block_tables, lengths,
                                  layer, interpret=interpret)


def _paged_attention_sharded(q, k_pages, v_pages, block_tables, lengths,
                             layer, *, interpret: bool, mesh):
    """shard_map-partitioned paged-attention kernel over an active mesh.

    KV split: block-table page dim over "model" when divisible — lengths
    are rebased per shard (``length - shard * pages_local * block_size``)
    so the kernel's inclusive ``pos <= length`` mask stays globally
    correct (far shards see a negative length = everything masked, which
    yields the neutral (m=-inf, l=0, acc=0) state).  Batch over "data"
    when divisible.  Page pools (every layer's) and the layer index ride
    in replicated.
    """
    axes = shd.mesh_axes_dict(mesh)
    model = axes.get("model", 1)
    data = axes.get("data", 1)
    B, H, D = q.shape
    bs = k_pages.shape[2]
    n_pages = block_tables.shape[1]
    batch_axis = "data" if (data > 1 and B % data == 0) else None
    kv_split = model > 1 and n_pages % model == 0
    pages_local = n_pages // model if kv_split else n_pages

    bt = jnp.asarray(block_tables, jnp.int32)
    ln = jnp.asarray(lengths, jnp.int32)
    ly = jnp.asarray(layer, jnp.int32)

    def run(q_l, kp, vp, bt_l, ln_l, ly_l):
        if kv_split:
            shard = jax.lax.axis_index("model")
            ln_shard = ln_l - shard * (pages_local * bs)
            acc, m, l = paged_attention_kernel(
                q_l, kp, vp, bt_l, ln_shard, ly_l, interpret=interpret,
                return_state=True)
            out = shd.combine_softmax_state(acc, m, l, "model")
            return out.reshape(q_l.shape).astype(q_l.dtype)
        return paged_attention_kernel(q_l, kp, vp, bt_l, ln_l, ly_l,
                                      interpret=interpret)

    fn = shd.shard_map(
        run, mesh=mesh,
        in_specs=(P(batch_axis, None, None),
                  P(None, None, None, None),
                  P(None, None, None, None),
                  P(batch_axis, "model" if kv_split else None),
                  P(batch_axis),
                  P()),
        out_specs=P(batch_axis, None, None))
    return fn(q, k_pages, v_pages, bt, ln, ly)
