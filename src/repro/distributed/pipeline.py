"""GPipe-style pipeline parallelism as a composable primitive.

§Perf-B identified pipeline parallelism as the remaining lever for
FSDP-gather-bound dense training (granite-34b class): stages keep their
weights resident and exchange only (microbatch, seq, d_model) activations —
per-chip wire cost ~microbatches x activation bytes instead of ~3 x params.

``pipeline_apply`` runs a homogeneous stage function over ``n_stages``
stages sharded on a mesh axis, with the classic GPipe schedule expressed as
a ``shard_map`` + ``lax.ppermute`` rotation: at tick t, stage s processes
microbatch (t - s) and passes its output to stage s+1.  Bubble fraction is
(S-1)/(M+S-1); backward works through JAX autodiff of the whole schedule
(ppermute transposes to the reverse permutation automatically).

Napkin (granite-34b, 16 stages over "model", M=32 microbatches):
activations crossing each boundary per step ~ B.S.D.2 bytes = 12.9 GB / 16
chips = 0.8 GB/chip vs the measured 283 GB/chip FSDP gathers — ~350x less
wire, at the cost of a 32% bubble and stage-balanced weight residency.
Validated for exact equivalence with the sequential stack in
tests/test_pipeline.py; integrating it as a per-arch recipe is future work
(EXPERIMENTS.md §Perf-B).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import shard_map


def pipeline_apply(stage_fn: Callable, stage_params, x, *, mesh,
                   axis_name: str = "model", n_microbatches: int):
    """Run ``x`` through ``n_stages`` sequential stages, pipelined.

    stage_fn: (params_slice, activations) -> activations (same shape).
    stage_params: pytree with leading dim = n_stages (stacked stage slices).
    x: (global_batch, ...) activations; global_batch % n_microbatches == 0.
    Returns stage_{S-1}(... stage_0(x)), numerically identical to the
    sequential loop.
    """
    n_stages = mesh.shape[axis_name]
    B = x.shape[0]
    assert B % n_microbatches == 0, (B, n_microbatches)
    mb = B // n_microbatches
    micro = x.reshape(n_microbatches, mb, *x.shape[1:])

    def per_stage(params_local, micro_local):
        # params_local: (1, ...) this stage's slice;  micro_local: the full
        # microbatch queue, replicated (the scheduler feeds stage 0 only)
        params_here = jax.tree.map(lambda t: t[0], params_local)
        stage_id = jax.lax.axis_index(axis_name)
        n_ticks = n_microbatches + n_stages - 1
        buf = jnp.zeros_like(micro_local[0])

        def tick(carry, t):
            buf, outputs = carry
            # stage 0 ingests microbatch t; others use what arrived
            feed = jnp.where(t < n_microbatches,
                             micro_local[jnp.minimum(t, n_microbatches - 1)],
                             jnp.zeros_like(buf))
            inp = jnp.where(stage_id == 0, feed, buf)
            active = (t >= stage_id) & (t - stage_id < n_microbatches)
            out = stage_fn(params_here, inp)
            out = jnp.where(active, out, jnp.zeros_like(out))
            # rotate stage s -> s+1 (last stage's output falls off the ring)
            nxt = jax.lax.ppermute(
                out, axis_name,
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            # last stage banks its finished microbatch
            done_idx = t - (n_stages - 1)
            is_done = (stage_id == n_stages - 1) & (done_idx >= 0)
            outputs = jnp.where(
                is_done,
                outputs.at[jnp.maximum(done_idx, 0)].set(out),
                outputs)
            return (nxt, outputs), None

        outputs0 = jnp.zeros_like(micro_local)
        (_, outputs), _ = jax.lax.scan(tick, (buf, outputs0),
                                       jnp.arange(n_ticks))
        # outputs live on the last stage; broadcast so every shard returns
        # the same value (out_specs replicate over the stage axis)
        outputs = jax.lax.psum(
            jnp.where(stage_id == n_stages - 1, outputs,
                      jnp.zeros_like(outputs)), axis_name)
        return outputs

    fn = shard_map(
        per_stage, mesh=mesh,
        in_specs=(P(axis_name), P()),
        out_specs=P())
    out = fn(stage_params, micro)
    return out.reshape(B, *x.shape[1:])
