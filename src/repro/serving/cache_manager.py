"""Decode-cache managers: slot accounting base + the slab backing store.

Two backing stores sit behind one slot-level interface (``alloc`` / ``free``
/ ``insert`` / ``advance`` / ``cache_len_vector`` / ``divergence``):

  * **slab** (:class:`CacheManager`, this module) — ONE pooled decode cache
    of ``n_slots`` slots, each a fixed worst-case ``cache_T`` region.  Works
    for every family on the ``models/api.py`` surface — attention KV caches
    and O(1) recurrent state alike — because slot surgery is expressed as
    pytree ops over the family's own cache structure.
  * **paged** (:class:`repro.serving.block_pool.PagedCacheManager`) —
    fixed-size KV blocks allocated on demand through per-slot block tables,
    with automatic prefix sharing and copy-on-write.  Position-indexed KV
    families only.

A slot is the serving analogue of one PE-column (synchronization group) in
the quasi-sync array: it owns private state and advances at its own sequence
position while the pool steps as one batched unit.  ``make_cache_manager``
is the facade the engine uses to pick a store per ``ServeConfig``.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np
import jax.numpy as jnp


class BaseCacheManager:
    """Slot accounting shared by every backing store: occupancy, per-slot
    sequence positions, and the vectorized position bookkeeping that both
    ``advance`` and ``divergence`` read."""

    def __init__(self, cfg, n_slots: int):
        if n_slots < 1:
            raise ValueError("n_slots must be >= 1")
        self.cfg = cfg
        self.n_slots = n_slots
        self.lengths = np.zeros(n_slots, np.int32)   # per-slot seq position
        self._free_slots: List[int] = list(range(n_slots - 1, -1, -1))
        self._occupied = np.zeros(n_slots, bool)

    # -- slot accounting ----------------------------------------------------

    @property
    def n_free(self) -> int:
        return len(self._free_slots)

    @property
    def n_active(self) -> int:
        return self.n_slots - len(self._free_slots)

    def alloc(self, slot: Optional[int] = None) -> int:
        """Claim a free slot (LIFO order), or — with ``slot`` — claim that
        specific slot (a drafter's cache mirrors the target pool, so its
        slots must align with the target's, not with this manager's own
        free-list order)."""
        if not self._free_slots:
            raise RuntimeError("no free slot")
        if slot is None:
            slot = self._free_slots.pop()
        elif slot in self._free_slots:
            self._free_slots.remove(slot)
        else:
            raise RuntimeError(f"slot {slot} is not free")
        self._occupied[slot] = True
        return slot

    def free(self, slot: int):
        if not self._occupied[slot]:
            raise ValueError(f"slot {slot} is not occupied")
        self._occupied[slot] = False
        self.lengths[slot] = 0
        self._free_slots.append(slot)

    def advance(self, slots, counts=None):
        """Bump the sequence position of the given slots — by one token
        each (the classic decode step) or by per-slot ``counts`` (tokens
        COMMITTED by a speculative verify step, 1..K+1 per slot).  One
        vectorized scatter-add, not a per-slot Python loop."""
        idx = np.asarray(list(slots), np.intp)
        if counts is None:
            np.add.at(self.lengths, idx, 1)
        else:
            np.add.at(self.lengths, idx,
                      np.asarray(list(counts), np.int32))

    def cache_len_vector(self) -> jnp.ndarray:
        """(n_slots,) per-slot positions for ``decode_step``.  Free slots sit
        at 0: their writes land in regions never read for an admitted
        request (overwritten by the next ``insert`` in the slab store,
        pointed at the trash block in the paged store).

        A copy: on the CPU a device array may alias the host array it was
        made from, and ``advance`` rewrites ``lengths`` in place while a
        dispatched step may not have read it yet (the model drafter
        advances before it waits on its step)."""
        return jnp.asarray(self.lengths.copy())

    def divergence(self) -> int:
        """Spread of active-slot positions (the quasi-sync E analogue) —
        reads the same vectorized ``lengths``/``_occupied`` state that
        ``advance`` maintains."""
        active = self.lengths[self._occupied]
        if active.size == 0:
            return 0
        return int(active.max() - active.min())

    def admissible_prefix(self, requests) -> int:
        """How many front-of-queue requests could be admitted right now.
        The slab rule is one free slot per request; the paged store
        overrides this with its free-block budget."""
        return min(len(requests), self.n_free)


class CacheManager(BaseCacheManager):
    """Slab store: fixed-capacity per-slot KV / recurrent state.

    All device work (cache allocation, the jitted+donating slot insert, and
    — on a mesh — sharding) goes through the ``executor``; constructing the
    manager directly without one builds a default single-device executor.
    """

    def __init__(self, cfg, n_slots: int, cache_T: int, executor=None,
                 telemetry=None):
        from repro.serving.telemetry import NULL_TELEMETRY
        super().__init__(cfg, n_slots)
        self.cache_T = cache_T
        self.telemetry = telemetry if telemetry is not None else NULL_TELEMETRY
        if executor is None:
            from repro.serving.executor import make_executor
            executor = make_executor(cfg)
        self.executor = executor
        self.cache = executor.zeros_cache(n_slots, cache_T)

    def fits(self, prompt_len: int, max_new_tokens: int) -> bool:
        """Does prompt + generation fit in one slot's capacity?"""
        return prompt_len + max_new_tokens <= self.cache_T

    # -- cache surgery ------------------------------------------------------

    def insert(self, slot: int, src_cache, length: int, src_index: int = 0,
               tokens=None):
        """Install request ``src_index`` of a prefill cache (padded to this
        pool's cache_T) into ``slot`` and set its sequence position.
        ``tokens`` is accepted for interface parity with the paged store
        (which needs the prompt for prefix sharing) and ignored here."""
        if not self._occupied[slot]:
            raise ValueError(f"slot {slot} must be alloc()ed before insert")
        # executor op: jitted once per executor (one compiled insert covers
        # every (slot, src_index) pair), pool buffer donated in place
        with self.telemetry.span("slot_insert", slot=slot, length=length):
            self.cache = self.executor.slot_insert(self.cache, src_cache,
                                                   slot, src_index)
        self.lengths[slot] = length

    def update(self, new_cache):
        """Adopt the cache returned by a batched decode step."""
        self.cache = new_cache


def make_cache_manager(cfg, n_slots: int, cache_T: int, *,
                       backend: str = "slab", block_size: int = 16,
                       num_blocks: Optional[int] = None,
                       executor=None, telemetry=None,
                       faults=None) -> BaseCacheManager:
    """Facade: build the backing store selected by ``backend``, with its
    device ops routed through ``executor`` (None -> single-device), its
    spans on ``telemetry`` (None -> no-op), and — paged only — its pool
    allocations checked against the ``faults`` injector (None -> no-op)."""
    if backend == "slab":
        return CacheManager(cfg, n_slots, cache_T, executor=executor,
                            telemetry=telemetry)
    if backend == "paged":
        from repro.serving.block_pool import PagedCacheManager
        return PagedCacheManager(cfg, n_slots, cache_T,
                                 block_size=block_size, num_blocks=num_blocks,
                                 executor=executor, telemetry=telemetry,
                                 faults=faults)
    raise ValueError(f"unknown cache_backend {backend!r}; "
                     f"expected 'slab' or 'paged'")
