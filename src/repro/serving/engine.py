"""Serving engine: static batched generation + quasi-sync continuous batching.

Two paths over the same ``models/api.py`` init/prefill/decode surface:

  * ``generate(batch)`` — the static path: one prefill, then the whole batch
    decodes in lock-step until every sequence finishes.  The decode loop is
    device-resident: a jitted multi-token ``lax.scan`` advances ``chunk``
    tokens per dispatch with sampling (greedy argmax / temperature
    categorical) fused into the step, so only ``(B,)`` tokens and done flags
    cross to the host per chunk — never the full ``(B, V)`` logits.  EOS
    early-exit is checked at chunk boundaries and the output is trimmed to
    the exact step the per-token loop would have stopped at.
  * ``serve(requests)`` — continuous batching: a slot pool (``CacheManager``)
    decodes with per-slot sequence positions, finished sequences are evicted
    mid-flight, and waiting requests are admitted into freed slots under the
    ``QuasiSyncScheduler``'s bounded lead window (the paper's inter-group
    elasticity E, one level up).  Greedy outputs are token-identical to the
    static path; throughput on heterogeneous-length workloads is not.

The engine is HOST-SIDE ORCHESTRATION ONLY.  Everything device-shaped —
jit tracing, matmul-backend scoping, device/mesh placement, cache
allocation, and buffer donation — lives behind ``serving/executor.py``:
the default :class:`SingleDeviceExecutor`, or a :class:`MeshExecutor`
running the same engine tensor-parallel over a ``("data", "model")`` mesh
(``ServeConfig.mesh_shape``) with token-identical greedy outputs.  One
``serve()`` call's loop state is a :class:`ServeLoop`: admission,
preemption, and decode stepping are its unit-testable methods.

Inference fast path: when a ``bp_*`` matmul mode is active the engine
pre-quantizes every dense kernel to int8 + per-channel scale once at
construction (``quantize_dense_params``) before handing params to the
executor, so no call path under ``serve``/``generate`` re-quantizes weights
per decode step.

Supports all 10 architectures (KV caches for attention families, recurrent
state for RWKV/Zamba), greedy and temperature sampling, per-sequence EOS
early exit, and BitParticle deployment estimates (per-layer bit sparsity ->
modeled cycles/energy) when a quantized matmul mode is active.
"""

from __future__ import annotations

import collections
import concurrent.futures
import dataclasses
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.bp_matmul import resolve_matmul_backend
from repro.models.layers import quantize_dense_params
from repro.serving.block_pool import NoFreeBlocks, PagedCacheManager
from repro.serving.cache_manager import make_cache_manager
from repro.serving.executor import Executor, make_executor
from repro.serving.faults import (NULL_INJECTOR, DrafterFault, FaultInjector,
                                  InjectedFault, StepCompileError,
                                  StepFault, StepTimeout)
from repro.serving.probe import NULL_PROBE, SparsityProbe, probe_supported
from repro.serving.queue import Request, RequestQueue, RequestState
from repro.serving.scheduler import (QuasiSyncScheduler, SchedulerConfig,
                                     prefill_bucket_len)
from repro.serving.telemetry import (CLOCK_ORIGIN, SCHEMA_VERSION,
                                     PhaseClock, Telemetry, percentiles,
                                     reduce_stream)

#: errors the serve loop survives via rebuild-and-replay recovery: injected
#: faults that exhausted their retry budget, watchdog aborts, and wrapped
#: real executor failures.  Everything else (config/user errors) raises.
RECOVERABLE_ERRORS = (InjectedFault, StepTimeout, StepFault)


@dataclasses.dataclass
class ServeConfig:
    max_new_tokens: int = 32
    temperature: float = 0.0          # 0 => greedy
    eos_id: Optional[int] = None
    cache_margin: int = 8             # extra cache slots beyond prompt+new
    decode_chunk: int = 8             # tokens per jitted decode scan dispatch
    # decode-cache backing store: "slab" reserves a worst-case cache_T
    # region per slot; "paged" allocates fixed-size KV blocks on demand
    # with prefix sharing + copy-on-write (position-indexed KV families)
    cache_backend: str = "slab"
    block_size: int = 16              # tokens per KV block (paged backend)
    # (data, model) mesh shape for tensor-parallel serving; None = single
    # device.  Requires prod(mesh_shape) visible jax devices.
    mesh_shape: Optional[Tuple[int, int]] = None
    # chunked prefill: bound the per-step prefill cost.  A prompt longer
    # than ``prefill_chunk`` is admitted on its first chunk only; the rest
    # of the prompt rides the multi-token verify step — at most
    # ``prefill_chunk`` prompt tokens per batched step, interleaved with
    # every other slot's decode — so a 10k-token prompt cannot stall
    # in-flight decoders for its whole prefill.  The final chunk's argmax
    # IS the first generated token (token-identical to one-shot prefill by
    # construction).  Greedy-only; needs a multi-token verify family
    # (dense/moe/vlm).  None = off (classic one-shot prefill).
    prefill_chunk: Optional[int] = None
    # speculative decoding: "none" | "prompt_lookup" (weight-free n-gram
    # drafter) | "model" (small same-family draft model — pass draft_cfg/
    # draft_params to the engine).  Greedy-only; outputs stay token-
    # identical to non-speculative greedy, steps shrink with acceptance.
    draft: str = "none"
    num_draft_tokens: int = 4         # K: drafts verified per step
    # observability: a ``serving.telemetry.Telemetry`` handle (metrics JSONL
    # / Chrome-trace / jax.profiler sinks).  None (the default) builds a
    # disabled no-op handle — no files written, token-identical outputs.
    telemetry: Optional[Telemetry] = None
    # hardware-cost observability: a ``serving.probe.SparsityProbe`` handle.
    # When enabled, probed step-fn variants measure activation bit/value
    # sparsity on-device (every ``probe_every`` decode steps + every
    # admission prefill) and each sample is priced through the paper's cost
    # models into an ``hw_estimate`` record.  None = NULL_PROBE, a strict
    # no-op pinned token-identical (docs/observability.md).
    probe: Optional[SparsityProbe] = None
    # -- robustness (docs/robustness.md) ------------------------------------
    # fault injection: a ``serving.faults.FaultInjector`` threaded to the
    # executor / cache managers / block pool / drafter exactly like the
    # telemetry handle.  None = the no-op NULL_INJECTOR (pinned a strict
    # no-op by token-identity tests).
    faults: Optional[FaultInjector] = None
    # bounded retry on transient (injected) step faults, with exponential
    # backoff base retry_backoff_s * 2**attempt (0 = immediate retry)
    max_step_retries: int = 2
    retry_backoff_s: float = 0.0
    # full rebuild-and-replay recoveries allowed per serve() before the
    # loop fails every in-flight request and returns
    max_recoveries: int = 3
    # wall-clock watchdog: abort any single dispatch exceeding this budget
    # (None = no watchdog; the aborted step recovers like a failed one)
    step_timeout_s: Optional[float] = None
    # degradation ladder thresholds: consecutive drafter faults before
    # speculation is disabled; recoveries before a non-XLA matmul backend
    # falls back to the XLA oracle; preemptions between ladder checks
    # before the lead window is halved (0 disables the rung)
    drafter_fault_limit: int = 2
    kernel_fault_limit: int = 2
    pool_pressure_limit: int = 8


def tokens_per_second(n_tokens: int, decode_s: float, prefill_s: float = 0.0,
                      steps: Optional[int] = None) -> float:
    """THE tokens/s rule for both engine paths: tokens over decode wall
    time — unless no decode step ran (everything finished at prefill), in
    which case the generated tokens are reported over total wall time
    instead of a blind 0.0."""
    if steps == 0:
        return n_tokens / max(prefill_s + decode_s, 1e-9)
    return n_tokens / max(decode_s, 1e-9)


# THE percentile rule lives in serving.telemetry now so the report and the
# benchmark scripts share one implementation; alias kept for existing callers.
_percentiles = percentiles


@dataclasses.dataclass
class GenerationResult:
    tokens: np.ndarray                # (B, <=max_new_tokens)
    prefill_s: float
    decode_s: float
    steps: int

    @property
    def decode_tokens_per_s(self) -> float:
        n = self.tokens.shape[0] * self.tokens.shape[1]
        return tokens_per_second(n, self.decode_s, self.prefill_s,
                                 self.steps)


@dataclasses.dataclass
class RequestResult:
    request_id: int
    tokens: np.ndarray                # generated tokens (incl. EOS if hit)
    prompt_len: int
    arrival_time: float
    ttft_steps: Optional[float]       # decode-step clock
    latency_steps: Optional[float]
    finish_reason: str
    ttft_wall_s: Optional[float] = None   # wall clock, queue entry -> tok 0


@dataclasses.dataclass
class ServeReport:
    results: List[RequestResult]
    prefill_s: float
    decode_s: float
    steps: int                        # batched decode steps executed
    n_syncs: int                      # admission (prefill) syncs
    n_rejected: int
    total_new_tokens: int
    slot_utilization: float           # mean occupied-slot fraction per step
    max_divergence: int               # max spread of per-slot positions
    deployment: Optional[dict] = None # BitParticle per-layer cycle/energy
    cache_backend: str = "slab"
    n_preemptions: int = 0            # paged: requests requeued on pool-dry
    prefix_hit_blocks: int = 0        # paged: trie hits adopted by reference
    cow_blocks: int = 0               # paged: copy-on-write block copies
    peak_blocks_in_use: int = 0       # paged: max live blocks at any step
    peak_active_slots: int = 0        # max concurrently-decoding requests
    mesh_shape: Optional[Tuple[int, int]] = None  # executor mesh (None=1dev)
    # speculative decoding (draft != "none")
    draft: str = "none"
    drafted_tokens: int = 0           # drafts submitted to verify steps
    accepted_tokens: int = 0          # drafts the target's argmax confirmed
    committed_tokens_per_step: float = 0.0
    # wall-clock latency percentiles ({p50, p90, p99} seconds, or None when
    # no sample exists): TTFT from queue entry to first token, and the
    # inter-token gap pooled over every request's consecutive emissions
    ttft_wall: Optional[Dict[str, float]] = None
    itl_wall: Optional[Dict[str, float]] = None
    # queue-wait percentiles (wall seconds from queue entry to admission)
    # and per-SLO-class latency breakdown: class name -> {"n", "ttft_wall",
    # "itl_wall", "queue_wait"} — folded from the stream's per-request
    # records, so the JSONL file reproduces them exactly
    queue_wait: Optional[Dict[str, float]] = None
    slo_classes: Optional[Dict[str, dict]] = None
    # chunked prefill: prompt tokens ingested through bounded chunk steps
    chunk_tokens: int = 0
    # robustness (docs/robustness.md): lifecycle evictions + fault ledger
    n_cancelled: int = 0              # requests cancelled (API or chaos)
    n_timed_out: int = 0              # requests past deadline_s/ttft budget
    n_failed: int = 0                 # requests failed (NaN guard / abort)
    n_faults: int = 0                 # fault records (injected + detected)
    n_injected_faults: int = 0        # fault records with injected=True
    n_retries: int = 0                # transient-fault dispatch retries
    n_degrades: int = 0               # degradation-ladder transitions
    n_recoveries: int = 0             # rebuild-and-replay recoveries
    # hardware-cost probe: measured-traffic means over the run's
    # ``hw_estimate`` records (None when the probe was off / never sampled).
    # Unlike ``deployment`` — a static weights-only estimate — these numbers
    # come from the bit sparsity live requests actually exhibited.
    hw_measured: Optional[dict] = None

    @property
    def acceptance_rate(self) -> float:
        """Fraction of drafted tokens the verify step accepted."""
        if self.drafted_tokens == 0:
            return 0.0
        return self.accepted_tokens / self.drafted_tokens

    @property
    def decode_tokens_per_s(self) -> float:
        return tokens_per_second(self.total_new_tokens, self.decode_s,
                                 self.prefill_s, self.steps)

    def tokens_by_request(self) -> Dict[int, np.ndarray]:
        return {r.request_id: r.tokens for r in self.results}


class ServeLoop:
    """Host-side orchestration state of ONE ``serve()`` call.

    The former nested closures of ``ServingEngine.serve`` — arrival
    submission, victim picking, preemption, insert-with-preemption, and
    admission — are methods here so they can be unit-tested directly
    (``tests/test_serve_loop.py``) instead of only end-to-end.  The loop
    never touches jit or device placement: all device work goes through
    ``engine.executor``.
    """

    def __init__(self, engine: "ServingEngine", requests: Sequence[Request],
                 *, n_slots: int = 8, cache_T: Optional[int] = None,
                 sched_cfg: Optional[SchedulerConfig] = None,
                 extras: Optional[Dict[int, dict]] = None,
                 num_blocks: Optional[int] = None):
        self.engine = engine
        self.executor: Executor = engine.executor
        self.serve_cfg = engine.serve_cfg
        # observability: sinks ride the config's handle; a fresh disabled
        # handle otherwise.  The executors get the handle BEFORE any cache
        # is built so their host->device transfers count from step zero.
        self.tel: Telemetry = (self.serve_cfg.telemetry
                               if self.serve_cfg.telemetry is not None
                               else Telemetry())
        engine.executor.set_telemetry(self.tel)
        if engine.draft_executor is not None:
            engine.draft_executor.set_telemetry(self.tel)
        # the in-memory step-record stream: ALWAYS accumulated (host dicts,
        # negligible next to a device dispatch) so ``report()`` is a pure
        # reduction over it whether or not any sink is attached — the
        # aggregate counters and the stream can never disagree
        self.stream: List[dict] = []
        #: the clock origin of every record's ``ts_s``: the process's one
        #: origin, shared with the ``Tracer``'s Chrome-trace ``ts``
        self.clock_origin = CLOCK_ORIGIN
        # the one timing path for the loop's host phases: always stamped,
        # spans only when tracing is on; each step record takes what
        # accumulated since the previous one in its ``phases``
        self._phase = PhaseClock(self.tel)
        self._h2d_mark = int(self.tel.counters.get("h2d_bytes", 0))
        self._d2h_mark = int(self.tel.counters.get("d2h_bytes", 0))
        # fault injection rides the config exactly like telemetry; the
        # executors get the handle before any cache op can fire a check
        self.faults: FaultInjector = (self.serve_cfg.faults
                                      if self.serve_cfg.faults is not None
                                      else NULL_INJECTOR)
        self.faults.bind(self._emit_injected)
        engine.executor.set_faults(self.faults)
        # sparsity probe rides the config exactly like telemetry/faults;
        # validate support up front so a misconfigured probe fails at loop
        # construction, not at the first probed trace
        self.probe: SparsityProbe = (self.serve_cfg.probe
                                     if self.serve_cfg.probe is not None
                                     else NULL_PROBE)
        if self.probe.enabled and not probe_supported(engine.cfg):
            raise ValueError(
                f"ServeConfig.probe: sparsity probe unsupported for "
                f"family={engine.cfg.family!r} "
                f"matmul_mode={engine.cfg.matmul_mode!r} (needs a causal-LM "
                f"family in bp_exact/bp_approx mode)")
        engine.executor.set_probe(self.probe)
        # weight bit-sparsity is static during a serve: computed once from
        # the pre-quantized int8 weights at engine level (cached there)
        self._weight_profile = (engine.weight_sparsity_profile()
                                if self.probe.enabled else None)
        requests = sorted(requests,
                          key=lambda r: (r.arrival_time, r.request_id))
        self.requests = requests
        if cache_T is None:
            need = [r.prompt_len + r.max_new_tokens for r in requests] or [1]
            cache_T = max(need) + self.serve_cfg.cache_margin
        # chunked prefill: validate up front so a misconfigured loop fails
        # at construction, not at the first long prompt
        self.prefill_chunk = self.serve_cfg.prefill_chunk
        if self.prefill_chunk is not None:
            if self.prefill_chunk < 1:
                raise ValueError("prefill_chunk must be >= 1 (or None)")
            from repro.models import api as _api
            if not _api.supports_verify(engine.cfg):
                raise ValueError(
                    f"family {engine.cfg.family!r} has no multi-token "
                    f"verify path: chunked prefill feeds prompt chunks "
                    f"through verify_step; serve with prefill_chunk=None")
            if self.serve_cfg.temperature > 0:
                raise ValueError(
                    "chunked prefill is greedy-only (temperature == 0): "
                    "the final chunk's first token comes from the verify "
                    "step's fused argmax")
            if extras:
                raise ValueError(
                    "chunked prefill does not compose with per-request "
                    "extra prefill inputs (extras ride the one-shot "
                    "prefill only)")
        # slot -> next unfed prompt position for requests mid-chunked-
        # prefill (cleared on preemption/eviction; replay restarts chunks)
        self.chunking: Dict[int, int] = {}
        # constructor args kept so ``recover()`` can rebuild a fresh store
        self.n_slots = n_slots
        self._cache_T_arg = cache_T
        self._num_blocks = num_blocks
        self.cm = self._build_cm()
        self.paged = isinstance(self.cm, PagedCacheManager)
        # prefill caches must slice into whole blocks on the paged store
        self.cache_T = self.cm.prefill_T if self.paged else cache_T
        sched_cfg = sched_cfg if sched_cfg is not None else SchedulerConfig()
        if sched_cfg.prefill_bucketing is None:
            # pow2 buckets need right-padding-safe prefill: attention KV
            # families without per-request extra inputs
            ragged_ok = (engine.cfg.family not in ("ssm", "hybrid")
                         and not extras)
            sched_cfg = dataclasses.replace(
                sched_cfg, prefill_bucketing="pow2" if ragged_ok else "exact")
        self.rq = RequestQueue(max_waiting=sched_cfg.max_waiting,
                               on_reject=self._on_reject)
        self.sched = QuasiSyncScheduler(self.rq, self.cm, sched_cfg,
                                        telemetry=self.tel)
        self.sched.prefill_chunk = self.prefill_chunk
        self.ragged = self.sched.bucketing == "pow2"
        self.extras = extras
        # deque: submit_arrivals pops from the head every decode step, and
        # list.pop(0) is O(n) — O(n^2) over long request streams
        self.arrivals = collections.deque(requests)
        self.active: Dict[int, Request] = {}      # slot -> request
        self.last_tok = np.zeros(n_slots, np.int32)
        self.slot_keys = np.zeros((n_slots, 2), np.uint32)
        self.now = 0.0
        self.prefill_s = 0.0
        self.decode_s = 0.0
        self.n_preemptions = 0
        self.peak_active = 0
        # robustness state: pending cancellations, recovery/ladder counters,
        # and whether any request carries a wall-clock deadline (the sweep
        # stays O(1) when nothing can cancel or expire)
        self._cancel_ids: Set[int] = set()
        self._any_deadlines = any(
            r.deadline_s is not None or r.ttft_deadline_s is not None
            for r in requests)
        self.n_recoveries = 0
        self._drafter_faults = 0
        self._pressure_mark = 0
        #: optional test/debug hook called after every loop iteration
        self.on_step_end: Optional[Callable[["ServeLoop"], None]] = None
        #: optional streaming hook: called once per FRESHLY emitted token
        #: with (request, token, index) — replay re-emissions after a
        #: preemption are suppressed (the client already received them),
        #: so a streaming consumer sees each position exactly once
        self.on_token: Optional[Callable[[Request, int, int], None]] = None
        # live-serving inbox: thread-safe dynamic submission for
        # ``run_forever`` (the front door's replica workers push here);
        # ``close()`` lets the loop drain and return
        self._inbox: List[Request] = []
        self._inbox_lock = threading.Lock()
        self._closed = False
        # cost hint (cost-aware routing): running mean of modeled
        # BitParticle array cycles per processed token over the probe's
        # ``hw_estimate`` samples (0.0 until the first sample)
        self._hw_cycles_sum = 0.0
        self._hw_tokens_sum = 0
        # speculative decoding: a drafter proposes up to K tokens per slot,
        # one multi-token verify step checks them all, slots commit a
        # VARIABLE 1..K+1 tokens per step (greedy-only, token-identical)
        from repro.serving.speculative import make_drafter
        self.drafter = make_drafter(self.serve_cfg, engine,
                                    n_slots=n_slots, cache_T=self.cache_T,
                                    telemetry=self.tel)
        self.draft_name = (self.drafter.name if self.drafter is not None
                           else "none")
        if self.drafter is not None:
            self.drafter.faults = self.faults
        self.n_drafted = 0
        self.n_accepted = 0
        self._bind_step_fns()
        mesh = self.executor.mesh
        self._emit("run",
                   cache_backend=str(self.serve_cfg.cache_backend),
                   n_slots=int(n_slots), cache_T=int(self.cache_T),
                   draft=self.draft_name,
                   temperature=float(self.serve_cfg.temperature),
                   mesh_shape=(None if mesh is None else
                               [int(d) for d in mesh.devices.shape]),
                   block_size=int(self.serve_cfg.block_size),
                   probe_every=int(self.probe.probe_every))

    @property
    def _wall0(self) -> float:
        """Former name of :attr:`clock_origin`."""
        return self.clock_origin

    def _build_cm(self):
        return make_cache_manager(self.engine.cfg, self.n_slots,
                                  self._cache_T_arg,
                                  backend=self.serve_cfg.cache_backend,
                                  block_size=self.serve_cfg.block_size,
                                  num_blocks=self._num_blocks,
                                  executor=self.engine.executor,
                                  telemetry=self.tel, faults=self.faults)

    def _bind_step_fns(self):
        """(Re-)fetch the jitted step entry points from the executor —
        called at construction and again after a recovery rebuild or a
        matmul-backend downgrade invalidates the executor's trace cache."""
        self._decode_fn = self.engine.executor.decode_sample_fn(
            self.serve_cfg.temperature, paged=self.paged)
        # the multi-token verify entry point serves BOTH speculation and
        # chunked prefill (a chunk step feeds known prompt tokens where
        # speculation feeds drafts); keep it bound while either needs it —
        # the ladder may null the drafter mid-run with chunking still on
        need_verify = (self.drafter is not None
                       or self.prefill_chunk is not None)
        if need_verify:
            self._verify_fn = self.engine.executor.verify_sample_fn(
                paged=self.paged)
        # probed variants are SEPARATE jits (the unprobed traces stay
        # byte-identical to a probe-less serve); sampled steps swap fns
        self._decode_probe_fn = self._verify_probe_fn = None
        if self.probe.enabled:
            self._decode_probe_fn = self.engine.executor.decode_sample_fn(
                self.serve_cfg.temperature, paged=self.paged, probed=True)
            if need_verify:
                self._verify_probe_fn = (
                    self.engine.executor.verify_sample_fn(paged=self.paged,
                                                          probed=True))

    # -- telemetry plumbing --------------------------------------------------

    def _emit(self, kind: str, *, at: Optional[float] = None,
              **fields) -> dict:
        """Append one record to the step stream and forward it to the
        metrics sink.  Values must already be native Python scalars — the
        JSONL line and the in-memory record are the SAME dict, which is
        what makes the file reduction byte-equal to the live one.  ``at``
        is the record's ``perf_counter`` stamp (now, by default)."""
        at = time.perf_counter() if at is None else at
        rec = {"schema": SCHEMA_VERSION, "kind": kind,
               "ts_s": at - self.clock_origin}
        rec.update(fields)
        self.stream.append(rec)
        self.tel.emit(rec)
        return rec

    def _on_reject(self, req: Request):
        self._emit("reject", step=int(self.sched.n_decode_steps),
                   request_id=int(req.request_id))

    def _step_clock(self) -> int:
        # the injector can fire during construction, before the scheduler
        # exists; everything after __init__ reads the real step clock
        sched = getattr(self, "sched", None)
        return int(sched.n_decode_steps) if sched is not None else 0

    def _emit_injected(self, site: str, **ctx) -> None:
        """Telemetry callback bound into the fault injector: every fired
        injection becomes a stream ``fault`` record with ``injected=True``
        (the chaos suite audits the stream 1:1 against the injector's
        ledger)."""
        self._emit("fault", step=self._step_clock(), site=site,
                   injected=True, **ctx)

    def _byte_deltas(self) -> Tuple[int, int]:
        """Host<->device bytes moved since the previous step record."""
        c = self.tel.counters
        h2d, d2h = int(c.get("h2d_bytes", 0)), int(c.get("d2h_bytes", 0))
        out = (h2d - self._h2d_mark, d2h - self._d2h_mark)
        self._h2d_mark, self._d2h_mark = h2d, d2h
        return out

    def _pool_gauges(self) -> dict:
        """Block-pool gauges for one step record (zeros on the slab store).
        Hit/CoW/peak counters are CUMULATIVE snapshots — monotone, so the
        stream reduction recovers the totals with a running max."""
        if not self.paged:
            return {"blocks_in_use": 0, "prefix_hit_blocks": 0,
                    "cow_blocks": 0, "peak_blocks_in_use": 0}
        pool = self.cm.pool
        return {"blocks_in_use": int(pool.n_live),
                "prefix_hit_blocks": int(pool.n_prefix_hits),
                "cow_blocks": int(pool.n_cow),
                "peak_blocks_in_use": int(pool.peak_live)}

    def _emit_hw(self, stats_np: np.ndarray, phase: str,
                 n_tokens: int = 1) -> None:
        """Fold one sampled step's device stats through the probe's cost
        models into an ``hw_estimate`` record plus Chrome-trace counter
        tracks (perfetto renders them alongside the phase spans).
        ``n_tokens`` is the tokens this step processed (prompt + committed
        + chunk-fed) — the denominator of the running cycles/token cost
        hint the front-door router reads for cost-aware routing."""
        fields = self.probe.fold(stats_np, self._weight_profile, phase)
        self._hw_cycles_sum += float(fields["array_cycles_per_step"])
        self._hw_tokens_sum += max(int(n_tokens), 1)
        self._emit("hw_estimate", step=int(self.sched.n_decode_steps),
                   **fields)
        self.tel.counter("sparsity",
                         act_bit=fields["act_bit_sparsity"],
                         act_value=fields["act_value_sparsity"],
                         weight_bit=fields["weight_bit_sparsity"])
        self.tel.counter("hw_model",
                         array_utilization=fields["array_utilization"],
                         cycles_bp_exact=fields["cycles"]["bp_exact"],
                         energy_bp_exact_pj=fields["mac_energy_pj"]
                         ["bp_exact"])

    @property
    def cost_hint_cycles_per_token(self) -> float:
        """Running mean of modeled BitParticle array cycles per processed
        token over the probe's sampled steps (0.0 with no sample / probe
        off) — the per-replica cost hint surfaced on router stats."""
        if self._hw_tokens_sum == 0:
            return 0.0
        return self._hw_cycles_sum / self._hw_tokens_sum

    # -- lifecycle: cancellation + deadlines --------------------------------

    def _live_requests(self) -> List[Request]:
        """Every request still in flight: not yet submitted, waiting, or
        active in a slot (terminal requests are no longer reachable)."""
        return (list(self.arrivals) + list(self.rq.peek())
                + list(self.active.values()))

    def _evict(self, slot: int) -> Request:
        """Remove ``slot``'s request from the batch and release every
        resource it holds (cache slot / block table, drafter state)."""
        req = self.active.pop(slot)
        self.chunking.pop(slot, None)
        self.cm.free(slot)
        if self.drafter is not None:
            self.drafter.on_free(slot)
        return req

    def sweep(self):
        """Run once per loop iteration BEFORE planning admissions: collect
        injector- and API-requested cancellations, then expire requests
        whose wall-clock deadline passed.  Evicted actives free their slot
        and blocks immediately, so the very next admission plan sees the
        reclaimed capacity."""
        with self._phase("loop.sweep", "sweep_s"):
            if self.faults.enabled:
                live = [int(r.request_id) for r in self._live_requests()]
                self._cancel_ids.update(self.faults.cancel_requests(live))
            pending = self.engine._pending_cancels
            if pending:
                self._cancel_ids.update(pending)
                pending.clear()
            if self._cancel_ids:
                self._apply_cancels()
            if self._any_deadlines:
                self._apply_deadlines()

    def _finish_evicted(self, req: Request, reason: str, kind: str,
                        where: str, **fields):
        req.finish(self.now, reason)
        self._emit(kind, step=int(self.sched.n_decode_steps),
                   request_id=int(req.request_id), where=where, **fields)

    def _apply_cancels(self):
        ids, self._cancel_ids = self._cancel_ids, set()
        for req in [r for r in self.arrivals if int(r.request_id) in ids]:
            self.arrivals.remove(req)
            self._finish_evicted(req, "cancelled", "cancel", "arrivals")
        for req in [r for r in self.rq.peek() if int(r.request_id) in ids]:
            self.rq.remove(req)
            self._finish_evicted(req, "cancelled", "cancel", "waiting")
        for slot in [s for s, r in self.active.items()
                     if int(r.request_id) in ids]:
            req = self._evict(slot)
            self._finish_evicted(req, "cancelled", "cancel", "active")
        # ids for unknown/already-finished requests are dropped silently:
        # cancel() is idempotent and may race a natural finish

    def _apply_deadlines(self):
        wall = time.perf_counter()

        def expired(req: Request) -> Optional[str]:
            t0 = req.wall_submitted_at
            if t0 is None:
                return None       # not yet submitted: deadlines start then
            if (req.ttft_deadline_s is not None
                    and req.first_token_at is None
                    and wall - t0 >= req.ttft_deadline_s):
                return "ttft"
            if (req.deadline_s is not None
                    and wall - t0 >= req.deadline_s):
                return "total"
            return None

        for req in list(self.rq.peek()):
            which = expired(req)
            if which is not None:
                self.rq.remove(req)
                self._finish_evicted(req, "timeout", "timeout", "waiting",
                                     deadline=which)
        for slot in list(self.active):
            req = self.active[slot]
            which = expired(req)
            if which is not None:
                self._evict(slot)
                self._finish_evicted(req, "timeout", "timeout", "active",
                                     deadline=which)

    # -- fault-hardened dispatch --------------------------------------------

    def _with_watchdog(self, fn):
        """Run one device dispatch under the wall-clock watchdog.  The jit
        call runs in a worker thread; if it exceeds the budget the loop
        raises :class:`StepTimeout` and recovery rebuilds the executor —
        the stuck computation's results are never adopted."""
        budget = self.serve_cfg.step_timeout_s
        if budget is None:
            return fn()
        pool = concurrent.futures.ThreadPoolExecutor(max_workers=1)
        try:
            fut = pool.submit(fn)
            try:
                return fut.result(timeout=budget)
            except concurrent.futures.TimeoutError:
                raise StepTimeout(
                    f"step exceeded the {budget:g}s watchdog budget"
                ) from None
        finally:
            pool.shutdown(wait=False, cancel_futures=True)

    def _dispatch(self, site: str, fn):
        """Fault boundary around one device dispatch: bounded retry with
        exponential backoff on injected transients (raised BEFORE the jit
        call, so the donated cache is untouched and a retry is safe), and
        runtime failures of an executed step (``JaxRuntimeError``) wrapped
        into :class:`StepFault` so ``run()`` can tell recoverable
        infrastructure faults from plain bugs.  A step that fails to
        trace, lower or compile (:class:`StepCompileError`) and every other
        exception propagate unwrapped: the run fails instead of retrying,
        rebuilding or falling back to XLA."""
        attempt = 0
        while True:
            try:
                return self._with_watchdog(fn)
            except (StepTimeout, StepCompileError):
                raise
            except InjectedFault as e:
                if attempt >= self.serve_cfg.max_step_retries:
                    raise
                attempt += 1
                self._emit("retry", step=int(self.sched.n_decode_steps),
                           site=str(getattr(e, "site", site)),
                           attempt=int(attempt))
                backoff = self.serve_cfg.retry_backoff_s
                if backoff > 0:
                    time.sleep(backoff * 2 ** (attempt - 1))
            except jax.errors.JaxRuntimeError as e:
                raise StepFault(site, e) from e

    def _maybe_inject_nan(self, step: dict, slots: List[int]) -> None:
        """Chaos only: poison the logits of injector-chosen slots with NaN
        inside the jitted step.  The mask key is added ONLY when an
        injector is live, so fault-free runs trace the exact same step
        structure as the seed."""
        if not self.faults.enabled:
            return
        bad = self.faults.nan_slots(slots)
        if not bad:
            return
        mask = np.zeros(self.n_slots, bool)
        mask[list(bad)] = True
        step["nan_mask"] = jnp.asarray(mask)

    def _fail_slot(self, slot: int):
        """The fused finite-logits guard flagged this slot (-1 sentinel):
        its logits were non-finite, so its stream cannot continue.  Fail
        just this request and release its resources — the batch survives."""
        req = self._evict(slot)
        req.finish(self.now, "failed")
        self._emit("fault", step=int(self.sched.n_decode_steps),
                   site="nan_guard", request_id=int(req.request_id),
                   slot=int(slot))

    # -- admission / preemption --------------------------------------------

    def submit_arrivals(self):
        """Move arrivals whose time has come into the waiting queue;
        requests that cannot ever fit the cache are rejected up front."""
        while self.arrivals and self.arrivals[0].arrival_time <= self.now:
            req = self.arrivals.popleft()
            req.wall_submitted_at = time.perf_counter()
            if not self.cm.fits(req.prompt_len, req.max_new_tokens):
                self.rq.reject(req, self.now)
                continue
            self.rq.submit(req, self.now)

    def pick_victim(self) -> Optional[int]:
        """Preemption victim: the most recently admitted active request —
        it has the least progress to replay (oldest requests keep theirs;
        unreferenced prefix-cache blocks were already reclaimed LRU-first
        by the pool)."""
        cands = [(req.admitted_at or 0.0, req.request_id, slot)
                 for slot, req in self.active.items()]
        if not cands:
            return None
        return max(cands)[2]

    def preempt(self, slot: int):
        """Evict ``slot``'s request back to the queue head with its
        generated tokens queued for token-exact replay."""
        req = self.active.pop(slot)
        # a mid-chunk preemption restarts chunked prefill on re-admission
        # (the emitted-token replay list still pins token identity)
        self.chunking.pop(slot, None)
        discarded = len(req.tokens)
        with self.tel.span("preempt", slot=slot,
                           request_id=req.request_id):
            self.cm.free(slot)
            if self.drafter is not None:
                self.drafter.on_free(slot)
            req.preempt()       # -> WAITING, tokens queued for replay
            self.rq.push_front(req)
        self.n_preemptions += 1
        self._emit("preempt", step=int(self.sched.n_decode_steps),
                   slot=int(slot), request_id=int(req.request_id),
                   discarded_tokens=int(discarded))
        # degradation ladder: sustained pool pressure (preemption churn)
        # halves the lead window — smaller admission bursts trade fusion
        # for fewer evictions
        lim = self.serve_cfg.pool_pressure_limit
        if (lim and self.n_preemptions - self._pressure_mark >= lim
                and self.sched.cfg.lead_window > 0):
            self._pressure_mark = self.n_preemptions
            new_e = self.sched.cfg.lead_window // 2
            self.sched.set_lead_window(new_e)
            self._emit("degrade", step=int(self.sched.n_decode_steps),
                       action="shrink_lead_window", lead_window=int(new_e))

    def insert_with_preemption(self, slot: int, cache, req: Request,
                               src_index: int,
                               length: Optional[int] = None):
        """Install a prefill cache into ``slot``, preempting actives (newest
        first) until the paged pool can cover the miss suffix.  ``length``
        is the prefilled prefix being installed (defaults to the full
        prompt; chunked admissions install only the first chunk)."""
        length = req.prompt_len if length is None else length
        while True:
            try:
                self.cm.insert(slot, cache, length,
                               src_index=src_index,
                               tokens=req.prompt[:length])
                return
            except NoFreeBlocks as e:
                # the inserting request holds no slot entry in `active`
                # yet, so it can never preempt itself here
                victim = self.pick_victim()
                if victim is None:
                    if isinstance(e, InjectedFault):
                        # injected exhaustion with nobody to preempt is an
                        # infrastructure fault — recoverable, not a sizing
                        # bug
                        raise
                    raise RuntimeError(
                        "paged pool cannot hold a single admitted "
                        "request; increase num_blocks")
                self.preempt(victim)

    def _stage_prefill(self, group: List[Request]):
        """Host side of one admission group's prefill: mark the requests
        admitted and build the padded token batch (plus extras).  Returns
        ``(batch, lens, eff, pad_to)``."""
        wall_admit = time.perf_counter()
        for req in group:
            req.transition(RequestState.PREFILL)
            req.admitted_at = self.now
            if req.wall_admitted_at is None:
                req.wall_admitted_at = wall_admit
        # chunked prefill: a long prompt is admitted on its FIRST chunk
        # only (bounded prefill cost); the remainder rides the multi-token
        # verify step, interleaved with every other slot's decode
        chunk = self.prefill_chunk
        eff = [r.prompt_len if chunk is None else min(r.prompt_len, chunk)
               for r in group]
        lens = np.asarray(eff, np.int32)
        # pow2 buckets: right-pad hetero prompts to one fused prefill
        # shape (valid rows are causal-mask-independent of the padding)
        pad_to = (prefill_bucket_len(int(lens.max()), self.cm.cache_T)
                  if self.ragged else int(lens.max()))
        toks = np.zeros((len(group), pad_to), np.int32)
        for j, r in enumerate(group):
            toks[j, :eff[j]] = r.prompt[:eff[j]]
        batch = {"tokens": toks}
        extras = self.extras
        if extras:
            keys = sorted({k for r in group
                           for k in (extras.get(r.request_id) or {})})
            if "positions" in keys:
                raise NotImplementedError(
                    "M-RoPE 'positions' is (3, B, S) — extras are "
                    "stacked on a leading batch axis and cannot "
                    "express it")
            for k in keys:
                missing = [r.request_id for r in group
                           if k not in (extras.get(r.request_id) or {})]
                if missing:
                    raise ValueError(
                        f"prefill group mixes requests with and without "
                        f"extra input {k!r} (missing for {missing})")
                batch[k] = np.stack(
                    [np.asarray(extras[r.request_id][k]) for r in group])
        self.tel.count("h2d_bytes", sum(int(np.asarray(v).nbytes)
                                        for v in batch.values()))
        return batch, lens, eff, pad_to

    def admit(self, group: List[Request], new_sync: bool = True):
        """Fused prefill of one admission group: run the prompts, sample
        (or replay) each request's first token, and install survivors into
        slots.  ``new_sync`` marks the group as opening a fresh admission
        sync in the metrics stream — ``run()`` passes True only for the
        first group of each ``plan_admissions()`` batch, so the stream's
        sync count matches the scheduler's."""
        engine = self.engine
        with self._phase("prefill.stage", "stage_s") as stage:
            batch, lens, eff, pad_to = self._stage_prefill(group)
        probed = self.probe.enabled   # every admission prefill is sampled

        def dispatch():
            if self.ragged:
                out = self.executor.prefill(batch, self.cache_T,
                                            prompt_lens=lens, probed=probed)
            else:
                out = self.executor.prefill(batch, self.cache_T,
                                            probed=probed)
            out[0].block_until_ready()
            return out

        with self._phase("prefill", "dispatch_s", group_size=len(group),
                         pad_to=pad_to) as ph:
            out = self._dispatch("prefill", dispatch)
        logits, cache = out[0], out[1]
        probe_stats = out[2] if probed else None
        wall = ph.t1
        self.prefill_s += ph.t1 - ph.t0
        n_emitted = 0
        with self._phase("install", "install_s", group_size=len(group)):
            for j, req in enumerate(group):
                chunked = eff[j] < req.prompt_len
                tok = None
                if not chunked:
                    if req.replay:
                        # preempted request: re-emit its original first token
                        tok = req.replay.pop(0)
                    else:
                        arr = np.asarray(engine._sample(
                            logits[j:j + 1], engine._request_key(req, 0)))
                        self.tel.count("d2h_bytes", arr.nbytes)
                        tok = int(arr[0])
                    self._append_token(req, tok, wall)
                    n_emitted += 1
                    if req.first_token_at is None:
                        req.first_token_at = self.now
                    reason = engine._finished(req, tok)
                    if reason is not None:
                        req.finish(self.now, reason)
                        continue
                slot = self.cm.alloc()
                try:
                    self.insert_with_preemption(slot, cache, req, j,
                                                length=eff[j])
                except BaseException:
                    # never leak the slot: a failed install (injected OOM
                    # past its retries, recoverable exhaustion) must leave
                    # the pool exactly as it found it
                    self.cm.free(slot)
                    raise
                req.slot = slot
                self.active[slot] = req
                if chunked:
                    # no token yet: the request stays PREFILL while the
                    # remaining prompt rides the chunk steps; its first
                    # token comes from the FINAL chunk's argmax
                    self.chunking[slot] = eff[j]
                    continue
                req.transition(RequestState.DECODE)
                self.last_tok[slot] = tok
                if self.serve_cfg.temperature > 0:
                    self.slot_keys[slot] = np.asarray(
                        engine._request_key_base(req))
                if self.drafter is not None:
                    self.drafter.on_admit(slot, req)
        if probe_stats is not None:
            # the stats array is the probe's only d2h traffic: count it
            # BEFORE the byte snapshot so this record carries it
            probe_stats = np.asarray(probe_stats)
            self.tel.count("d2h_bytes", int(probe_stats.nbytes))
        h2d, d2h = self._byte_deltas()
        gauges = self._pool_gauges()
        end = time.perf_counter()
        self._emit("prefill", at=end, step=int(self.sched.n_decode_steps),
                   wall_s=end - stage.t0,
                   phases=self._phase.take(end, "dispatch_s", "install_s"),
                   group_size=int(len(group)), pad_to=int(pad_to),
                   prompt_tokens=int(lens.sum()),
                   # every NON-CHUNKED request emits exactly one token at
                   # prefill (sampled or replayed), finished-at-prefill
                   # included; chunked admissions emit theirs at the final
                   # chunk step instead
                   committed_tokens=int(n_emitted),
                   new_sync=bool(new_sync),
                   active_slots=int(self.cm.n_active),
                   h2d_bytes=h2d, d2h_bytes=d2h, **gauges)
        if probe_stats is not None:
            self._emit_hw(probe_stats, "prefill",
                          n_tokens=int(lens.sum()) + n_emitted)

    def _append_token(self, req: Request, tok: int, wall: float):
        """Record one emitted token with its wall-clock stamp.  Replayed
        tokens (re-emitted after a preemption) keep their ORIGINAL stamps —
        they already streamed to the client once — so a stamp is only
        added once the token count grows past the recorded history.  Fresh
        emissions also fan out to the streaming hook (each position exactly
        once) and feed the scheduler's live SLO percentile windows."""
        req.tokens.append(tok)
        if len(req.wall_token_times) < len(req.tokens):
            req.wall_token_times.append(wall)
            n = len(req.wall_token_times)
            if n == 1:
                if req.wall_submitted_at is not None:
                    self.sched.observe_ttft(req.slo_class,
                                            wall - req.wall_submitted_at)
            else:
                self.sched.observe_itl(req.slo_class,
                                       wall - req.wall_token_times[-2])
            if self.on_token is not None:
                self.on_token(req, tok, len(req.tokens) - 1)

    # -- stepping -----------------------------------------------------------

    def writable_slots(self, counts: Optional[Dict[int, int]] = None
                       ) -> List[int]:
        """Active slots that can write this step's tokens — one per slot on
        the classic path, ``counts[slot]`` (committed token + drafts) under
        speculation.  On the paged store every slot must own writable
        blocks over its append span (allocate at block boundaries,
        copy-on-write shared tails); when the pool runs dry the newest
        admission is preempted and the check retried."""
        slots = list(self.active.keys())
        if not self.paged:
            return slots
        while slots:
            ns = None if counts is None else [counts.get(s, 1)
                                             for s in slots]
            if self.cm.prepare_append(slots, ns) is None:
                return slots
            self.preempt(self.pick_victim())   # newest admission goes
            slots = list(self.active.keys())
        return slots

    def decode_once(self, slots: List[int]):
        """One batched decode step: fixed (n_slots, ...) shapes, decode +
        fold + sample fused into ONE jitted dispatch with the cache buffer
        donated; only the (n_slots,) sampled tokens transfer to host.  The
        record's ``prepare_s`` is the caller's ``decode.prepare`` phase
        (``writable_slots``: block allocation / CoW on the paged store)."""
        with self._phase("decode.stage", "stage_s") as stage:
            counts = np.zeros(self.n_slots, np.uint32)
            for s in slots:
                counts[s] = len(self.active[s].tokens)
            step = {"tokens": jnp.asarray(self.last_tok[:, None]),
                    "cache_len": self.cm.cache_len_vector()}
            if self.paged:
                step["block_tables"] = self.cm.block_tables_device()
            self._maybe_inject_nan(step, slots)
            self.tel.count("h2d_bytes",
                           int(step["tokens"].nbytes)
                           + int(step["cache_len"].nbytes)
                           + int(self.slot_keys.nbytes)
                           + int(counts.nbytes))
            probed = self.probe.should_sample(int(self.sched.n_decode_steps))

        def dispatch():
            fn = self._decode_probe_fn if probed else self._decode_fn
            out = fn(self.cm.cache, step, jnp.asarray(self.slot_keys),
                     jnp.asarray(counts))
            out[0].block_until_ready()
            return out

        with self._phase("decode", "dispatch_s", n_slots=len(slots)) as ph:
            out = self._dispatch("decode", dispatch)
        wall = ph.t1
        self.decode_s += ph.t1 - ph.t0
        n_committed = 0
        with self._phase("commit", "commit_s", n_slots=len(slots)):
            toks, new_cache = out[0], out[1]
            probe_stats = np.asarray(out[2]) if probed else None
            self.cm.update(new_cache)
            self.cm.advance(slots)
            self.sched.observe_decode_step(n_committed=len(slots))
            # occupancy/divergence captured HERE (before finished slots
            # free) so the record sees exactly what the scheduler observed
            occupancy = self.cm.n_active / self.cm.n_slots
            divergence = int(self.cm.divergence())
            self.peak_active = max(self.peak_active, len(slots))
            self.now += 1.0
            toks_np = np.asarray(toks)
            self.tel.count("d2h_bytes", int(toks_np.nbytes))
            for slot in slots:
                req = self.active[slot]
                if req.replay:
                    # replaying a preemption: force the recorded token (the
                    # greedy resample equals it; this also pins temperature
                    # sampling to the original stream)
                    tok = req.replay.pop(0)
                else:
                    tok = int(toks_np[slot])
                    if tok < 0:
                        # non-finite logits (the fused guard's -1 sentinel):
                        # fail ONLY the poisoned slot — its KV state is
                        # suspect, everyone else's tokens commit normally
                        self._fail_slot(slot)
                        continue
                self._append_token(req, tok, wall)
                self.last_tok[slot] = tok
                n_committed += 1
                reason = self.engine._finished(req, tok)
                if reason is not None:
                    del self.active[slot]
                    self.cm.free(slot)
                    req.finish(self.now, reason)
        if n_committed != len(slots):
            # nan-guard failures committed nothing: correct the scheduler's
            # optimistic per-slot count (observed above, before the frees,
            # so occupancy accounting matches the fault-free path exactly)
            self.sched.n_committed_tokens -= len(slots) - n_committed
        if probe_stats is not None:
            self.tel.count("d2h_bytes", int(probe_stats.nbytes))
        h2d, d2h = self._byte_deltas()
        gauges = self._pool_gauges()
        end = time.perf_counter()
        self._emit("decode", at=end, step=int(self.sched.n_decode_steps),
                   wall_s=end - stage.t0,
                   phases=self._phase.take(end, "prepare_s", "dispatch_s",
                                           "commit_s"),
                   active_slots=int(len(slots)), n_slots=int(self.n_slots),
                   occupancy=occupancy, divergence=divergence,
                   committed_tokens=int(n_committed),
                   h2d_bytes=h2d, d2h_bytes=d2h, **gauges)
        if probe_stats is not None:
            self._emit_hw(probe_stats, "decode", n_tokens=n_committed)

    def decode_once_spec(self):
        """One fused multi-token step: speculative verification and/or
        chunked prefill over ONE (n_slots, S) forward pass.

        Decode slots ride it as speculation: draft up to K tokens, verify
        them all, commit the accepted prefix plus the target's own next
        token — 1..K+1 tokens per step, token-identical to classic greedy.
        Chunk slots (requests mid-chunked-prefill) feed their next <= S
        KNOWN prompt tokens instead: the model writes their KV at the
        slot's positions exactly as it would rejected drafts, the
        mid-chunk argmaxes are ignored (the true continuation is the
        prompt itself), and when the prompt is exhausted the FINAL fed
        position's argmax is the request's first generated token — so
        chunked prefill is token-identical to one-shot prefill by
        construction.  With no drafter (chunked prefill only), decode
        slots degenerate to single-token commits, exactly a classic step.

        Per-slot draft lengths are capped by the remaining output budget
        (committing past ``max_new_tokens`` is impossible, so drafting
        there is pure waste).  The step rides a fixed shape per mode —
        (n_slots, K+1) for pure speculation, (n_slots, max(K+1, chunk))
        when chunk slots are aboard — so compiled variants stay O(1);
        causality makes the wider shape's extra garbage columns inert."""
        # the drafter may be disabled mid-step by the degradation ladder;
        # slot bookkeeping below must keep using the one that drafted
        drafter = self.drafter
        K = self.serve_cfg.num_draft_tokens if drafter is not None else 0
        chunk_now = dict(self.chunking)
        S = (max(K + 1, self.prefill_chunk or 0) if chunk_now else K + 1)
        slots = list(self.active.keys())
        dec = [s for s in slots if s not in chunk_now]
        caps = {s: max(min(K, self.active[s].max_new_tokens
                           - len(self.active[s].tokens) - 1), 0)
                for s in dec}
        drafts = {}
        with self._phase("draft", "draft_s", n_slots=len(dec)) as draft_ph:
            if drafter is not None and any(caps.values()):
                try:
                    drafts = drafter.propose_all(
                        {s: self.active[s] for s in dec}, caps)
                    self._drafter_faults = 0
                except DrafterFault:
                    # a failed drafter costs speculation, never correctness:
                    # the step proceeds draft-less (1 committed token per
                    # slot, exactly a classic decode)
                    drafts = {}
                    self._drafter_faults += 1
                    lim = self.serve_cfg.drafter_fault_limit
                    if lim and self._drafter_faults >= lim:
                        self.drafter = None
                        self._emit("degrade",
                                   step=int(self.sched.n_decode_steps),
                                   action="disable_speculation")
        drafts = {s: np.asarray(drafts.get(s, ()), np.int32)[:caps[s]]
                  for s in dec}
        # chunk rows: the next <= S unfed prompt tokens per chunk slot
        feeds = {s: np.asarray(self.active[s].prompt[p:p + S], np.int32)
                 for s, p in chunk_now.items()}
        # the paged store needs writable blocks over each slot's full
        # append span; preemption inside may shrink the slot set
        spans = {s: len(drafts[s]) + 1 for s in dec}
        spans.update({s: len(feeds[s]) for s in chunk_now})
        with self._phase("decode.prepare", "prepare_s"):
            slots = self.writable_slots(spans)
        if not slots:
            return
        with self._phase("decode.stage", "stage_s"):
            # a preemption inside writable_slots evicts slots (and clears
            # their chunk state): refresh both memberships before building
            # rows
            live = set(slots)
            dec = [s for s in dec if s in live]
            chunk_now = {s: p for s, p in chunk_now.items() if s in live}
            toks = np.zeros((self.n_slots, S), np.int32)
            for s in dec:
                toks[s, 0] = self.last_tok[s]
                d = drafts[s]
                toks[s, 1:1 + len(d)] = d
            for s in chunk_now:
                toks[s, :len(feeds[s])] = feeds[s]
            step = {"tokens": jnp.asarray(toks),
                    "cache_len": self.cm.cache_len_vector()}
            if self.paged:
                step["block_tables"] = self.cm.block_tables_device()
            self._maybe_inject_nan(step, slots)
            self.tel.count("h2d_bytes", int(step["tokens"].nbytes)
                           + int(step["cache_len"].nbytes))
            probed = self.probe.should_sample(int(self.sched.n_decode_steps))

        def dispatch():
            fn = self._verify_probe_fn if probed else self._verify_fn
            out = fn(self.cm.cache, step)
            out[0].block_until_ready()
            return out

        with self._phase("verify", "dispatch_s", n_slots=len(slots)) as ph:
            out = self._dispatch("verify", dispatch)
        greedy, new_cache = out[0], out[1]
        probe_stats = np.asarray(out[2]) if probed else None
        wall = ph.t1
        self.decode_s += ph.t1 - ph.t0
        self.cm.update(new_cache)
        greedy_np = np.asarray(greedy)      # (n_slots, S) argmax stream
        self.tel.count("d2h_bytes", int(greedy_np.nbytes))
        drafted0, accepted0 = self.n_drafted, self.n_accepted
        commits: Dict[int, int] = {}        # cache POSITIONS advanced
        finished: Dict[int, str] = {}
        n_committed = 0                     # tokens EMITTED (decode output)
        n_chunk_fed = 0                     # prompt tokens fed (chunk rows)
        with self._phase("commit", "commit_s", n_slots=len(slots)):
            for slot in slots:
                req = self.active[slot]
                if slot in chunk_now:
                    # chunked prefill: the fed prompt tokens are ground
                    # truth, so the cache always advances by the feed span;
                    # only the FINAL chunk's last argmax is a real output
                    n = len(feeds[slot])
                    commits[slot] = n
                    n_chunk_fed += n
                    new_pos = chunk_now[slot] + n
                    if new_pos < req.prompt_len:
                        self.chunking[slot] = new_pos
                        continue
                    del self.chunking[slot]
                    if req.replay:
                        tok = req.replay.pop(0)
                    else:
                        tok = int(greedy_np[slot, n - 1])
                        if tok < 0:
                            finished[slot] = "failed"
                            continue
                    self._append_token(req, tok, wall)
                    if req.first_token_at is None:
                        req.first_token_at = self.now
                    req.transition(RequestState.DECODE)
                    self.last_tok[slot] = tok
                    n_committed += 1
                    if self.drafter is not None:
                        self.drafter.on_admit(slot, req)
                    reason = self.engine._finished(req, tok)
                    if reason is not None:
                        finished[slot] = reason
                    continue
                d = drafts[slot]
                # greedy accept: drafts match the target's argmax stream up
                # to the first miss; the miss position's argmax is the
                # bonus token
                m = 1
                while m <= len(d) and greedy_np[slot, m - 1] == d[m - 1]:
                    m += 1
                self.n_drafted += len(d)
                self.n_accepted += m - 1
                appended = 0
                for j in range(m):
                    if req.replay:
                        # replay equals the greedy stream (token identity
                        # holds across preemption under speculation too)
                        tok = req.replay.pop(0)
                    else:
                        tok = int(greedy_np[slot, j])
                        if tok < 0:
                            # fused finite-logits guard tripped: fail this
                            # slot at the poisoned position, keep the prefix
                            finished[slot] = "failed"
                            break
                    self._append_token(req, tok, wall)
                    self.last_tok[slot] = tok
                    appended += 1
                    reason = self.engine._finished(req, tok)
                    if reason is not None:
                        finished[slot] = reason
                        break
                commits[slot] = appended
                n_committed += appended
        # commit the positions, then roll the paged store's speculative
        # tail blocks back BEFORE any slot is freed (free() releases whole
        # tables; release_tail only ever touches private draft-span blocks)
        self.cm.advance(slots, [commits[s] for s in slots])
        if self.paged:
            with self._phase("rollback", "rollback_s", n_slots=len(slots)):
                for slot in slots:
                    self.cm.release_tail(slot)
        self.sched.observe_decode_step(n_committed=n_committed)
        occupancy = self.cm.n_active / self.cm.n_slots
        divergence = int(self.cm.divergence())
        self.peak_active = max(self.peak_active, len(slots))
        self.now += 1.0
        for slot in slots:
            if slot in finished:
                req = self.active.pop(slot)
                self.chunking.pop(slot, None)
                self.cm.free(slot)
                if drafter is not None:
                    drafter.on_free(slot)
                req.finish(self.now, finished[slot])
                if finished[slot] == "failed":
                    self._emit("fault", step=int(self.sched.n_decode_steps),
                               site="nan_guard",
                               request_id=int(req.request_id),
                               slot=int(slot))
            elif drafter is not None and slot not in chunk_now:
                # chunk slots have no drafter state: mid-chunk ones were
                # never admitted into it, just-completed ones had on_admit
                # called THIS step with the cache already at commit length
                drafter.observe_commit(slot,
                                       int(self.cm.lengths[slot]))
        if probe_stats is not None:
            self.tel.count("d2h_bytes", int(probe_stats.nbytes))
        h2d, d2h = self._byte_deltas()
        gauges = self._pool_gauges()
        end = time.perf_counter()
        self._emit("verify", at=end, step=int(self.sched.n_decode_steps),
                   wall_s=end - draft_ph.t0,
                   phases=self._phase.take(end, "draft_s", "prepare_s",
                                           "dispatch_s", "commit_s",
                                           "rollback_s"),
                   active_slots=int(len(slots)), n_slots=int(self.n_slots),
                   occupancy=occupancy, divergence=divergence,
                   committed_tokens=int(n_committed),
                   chunk_tokens=int(n_chunk_fed),
                   drafted_tokens=int(self.n_drafted - drafted0),
                   accepted_tokens=int(self.n_accepted - accepted0),
                   h2d_bytes=h2d, d2h_bytes=d2h, **gauges)
        if probe_stats is not None:
            self._emit_hw(probe_stats, "verify",
                          n_tokens=n_committed + n_chunk_fed)

    # -- live submission (the front door's entry points) --------------------

    def submit(self, request: Request) -> None:
        """Thread-safe dynamic submission for :meth:`run_forever`.  The
        request joins the arrival stream at the loop's next inbox drain;
        its ``arrival_time`` defaults to the loop's current virtual clock
        (stamped at drain) so step-clock metrics stay well-defined."""
        with self._inbox_lock:
            if self._closed:
                raise RuntimeError("serve loop is closed; cannot submit")
            request.wall_enqueued_at = time.perf_counter()
            self._inbox.append(request)

    def close(self) -> None:
        """Stop accepting submissions; :meth:`run_forever` returns once
        everything already in flight drains."""
        with self._inbox_lock:
            self._closed = True

    def _drain_inbox(self) -> None:
        with self._phase("loop.inbox", "inbox_s"):
            with self._inbox_lock:
                if not self._inbox:
                    return
                fresh, self._inbox = self._inbox, []
            drained = time.perf_counter()
            for req in fresh:
                req.wall_drained_at = drained
                if req.arrival_time <= 0.0:
                    req.arrival_time = self.now
                if (req.deadline_s is not None
                        or req.ttft_deadline_s is not None):
                    self._any_deadlines = True
                self.requests.append(req)
                self.arrivals.append(req)

    def run(self) -> ServeReport:
        """Drain the constructor-supplied request list to completion (the
        classic batch entry point — a pre-closed live loop)."""
        self.close()
        return self.run_forever(poll_s=0.0)

    def run_forever(self, poll_s: float = 0.001) -> ServeReport:
        """Serve until closed AND drained.  Identical to the classic
        :meth:`run` loop except that each iteration first drains the
        thread-safe inbox, and an idle (empty) loop parks for ``poll_s``
        instead of returning — :meth:`submit` wakes it, :meth:`close`
        lets it finish.  Returns the same :class:`ServeReport`."""
        self.tel.start_profile()
        try:
            with self.tel.span("serve"):
                with self._phase("loop.notify", "notify_s"):
                    self.submit_arrivals()
                while True:
                    self._drain_inbox()
                    if not (self.arrivals or len(self.rq) or self.active):
                        with self._phase("loop.idle", "idle_s"):
                            with self._inbox_lock:
                                done = self._closed and not self._inbox
                            if not done and poll_s > 0:
                                time.sleep(poll_s)
                        if done:
                            break
                        continue
                    # lifecycle sweep first: cancellations/expiries free
                    # capacity that this iteration's admission plan sees
                    self.sweep()
                    if (self.arrivals or len(self.rq) or self.active):
                        try:
                            self._step()
                        except RECOVERABLE_ERRORS as e:
                            self.recover(e)
                    # else the sweep terminalized the only work (e.g. a
                    # cancel): no step runs, but observers still hear of it
                    if self.on_step_end is not None:
                        with self._phase("loop.notify", "notify_s"):
                            self.on_step_end(self)
            self._emit_request_records()
            return self.report()
        finally:
            self.tel.stop_profile()
            self.tel.flush()

    def _emit_request_records(self) -> None:
        """One ``request`` record per submitted request at drain time: the
        stream-side source for queue-wait and per-SLO-class wall-latency
        percentiles, so ``reduce_stream`` over the JSONL file reproduces
        the report's numbers exactly (file/live parity)."""
        step = int(self.sched.n_decode_steps)

        def gap(a: Optional[float], b: Optional[float]) -> Optional[float]:
            return None if a is None or b is None else b - a

        for req in sorted(self.requests, key=lambda r: r.request_id):
            wt = req.wall_token_times
            # a copy: the front door may still be writing the last tokens
            written = list(req.wall_written_times)
            self._emit("request", step=step,
                       request_id=int(req.request_id),
                       slo_class=str(req.slo_class),
                       finish_reason=req.finish_reason,
                       n_tokens=int(len(req.tokens)),
                       queue_wait_s=gap(req.wall_submitted_at,
                                        req.wall_admitted_at),
                       ttft_wall_s=gap(req.wall_submitted_at,
                                       wt[0] if wt else None),
                       itl_wall_s=[b - a for a, b in zip(wt, wt[1:])],
                       ingress_s=gap(req.wall_received_at,
                                     req.wall_enqueued_at),
                       inbox_s=gap(req.wall_enqueued_at,
                                   req.wall_drained_at),
                       hold_s=gap(req.wall_drained_at,
                                  req.wall_submitted_at),
                       egress_s=[w - t for t, w in zip(wt, written)])

    def _step(self):
        """One loop iteration: admissions, then one batched decode/verify.
        Raising out of here with a RECOVERABLE error leaves no partial
        state — failed admissions are rolled back to the queue head."""
        with self._phase("loop.plan", "plan_s"):
            groups = self.sched.plan_admissions()
        try:
            # one plan_admissions() batch is ONE admission sync;
            # only its first group opens the sync in the stream
            for gi, group in enumerate(groups):
                self.admit(group, new_sync=(gi == 0))
        except RECOVERABLE_ERRORS:
            self._rollback_admissions(groups)
            raise
        if not self.active:
            if not len(self.rq) and self.arrivals:
                # idle: jump the virtual clock to the next arrival
                self.now = max(self.now, self.arrivals[0].arrival_time)
                with self._phase("loop.notify", "notify_s"):
                    self.submit_arrivals()
            return
        if self.drafter is not None or self.chunking:
            self.decode_once_spec()
        else:
            with self._phase("decode.prepare", "prepare_s"):
                slots = self.writable_slots()
            if not slots:
                return
            self.decode_once(slots)
        with self._phase("loop.notify", "notify_s"):
            self.submit_arrivals()

    def _rollback_admissions(self, groups: List[List[Request]]):
        """A recoverable fault escaped mid-admission: return every
        not-yet-installed request to the queue head (tokens it already
        emitted ride the replay list), newest last-pushed so the original
        admission order is preserved."""
        queued = set(map(id, self.rq.peek()))
        for group in reversed(groups):
            for req in reversed(group):
                if req.state is RequestState.PREFILL:
                    req.preempt()          # -> WAITING, tokens -> replay
                    self.rq.push_front(req)
                elif (req.state is RequestState.WAITING
                      and id(req) not in queued):
                    # WAITING but already queued happens when the faulting
                    # insert preempted a groupmate: preempt() requeued it,
                    # a second push would double-admit it later
                    self.rq.push_front(req)
                # DECODE (already installed) and terminal states stay put

    # -- recovery -----------------------------------------------------------

    def recover(self, error: BaseException):
        """Rebuild-and-replay after a recoverable step failure: preempt
        every active request (token-exact replay), rebuild the executor's
        trace cache and a FRESH backing store, and let the loop re-admit.
        Past ``max_recoveries`` the loop degrades to failing all in-flight
        requests so ``serve()`` always returns."""
        step = int(self.sched.n_decode_steps)
        site = str(getattr(error, "site", "executor"))
        if not isinstance(error, InjectedFault):
            # injected faults were already recorded at fire time; real
            # failures (StepFault/StepTimeout) get their record here
            self._emit("fault", step=step, site=site,
                       error=f"{type(error).__name__}: {error}")
        self.n_recoveries += 1
        if self.n_recoveries > self.serve_cfg.max_recoveries:
            self._fail_inflight(error)
            return
        with self.tel.span("recover", site=site):
            n_requeued = 0
            while self.active:
                self.preempt(self.pick_victim())
                n_requeued += 1
            self.executor.reset()
            # degradation ladder: repeated kernel-layer faults fall back
            # to the XLA oracle backend (correctness over speed)
            lim = self.serve_cfg.kernel_fault_limit
            if (lim and self.n_recoveries >= lim
                    and resolve_matmul_backend(
                        self.executor.matmul_backend) != "xla"):
                self.executor.set_matmul_backend("xla")
                self._emit("degrade", step=step, action="xla_fallback")
            self.cm = self._build_cm()
            self.sched.cache_mgr = self.cm
            self._bind_step_fns()
        self._emit("recover", step=step, n_requeued=int(n_requeued))

    def _fail_inflight(self, error: BaseException):
        """Terminal degradation: the recovery budget is spent.  Fail every
        in-flight request (releasing all slots/blocks) so the loop drains
        and ``serve()`` returns a report instead of hanging or raising."""
        self._emit("degrade", step=int(self.sched.n_decode_steps),
                   action="abort",
                   error=f"{type(error).__name__}: {error}")
        for slot in list(self.active):
            req = self._evict(slot)
            req.finish(self.now, "failed")
        for req in self.rq.pop(len(self.rq)):
            req.finish(self.now, "failed")
        while self.arrivals:
            self.arrivals.popleft().finish(self.now, "failed")

    def report(self) -> ServeReport:
        """Build the report as a PURE REDUCTION over the step-record stream
        (``telemetry.reduce_stream``): every aggregate counter is folded
        from the same records the metrics sink saw, so the report and the
        JSONL file can never disagree (pinned byte-equal by
        ``tests/test_telemetry.py``).  Only the per-request results and
        wall-clock latency percentiles come from the Request objects — they
        are per-request artifacts, not step aggregates."""

        def ttft_wall(r: Request) -> Optional[float]:
            if not r.wall_token_times or r.wall_submitted_at is None:
                return None
            return r.wall_token_times[0] - r.wall_submitted_at

        results = [
            RequestResult(
                request_id=r.request_id,
                tokens=np.asarray(r.tokens, np.int64),
                prompt_len=r.prompt_len,
                arrival_time=r.arrival_time,
                ttft_steps=r.ttft,
                latency_steps=r.latency,
                finish_reason=r.finish_reason or "unknown",
                ttft_wall_s=ttft_wall(r),
            )
            for r in sorted(self.requests, key=lambda r: r.request_id)
        ]
        itl = [b - a for r in self.requests
               for a, b in zip(r.wall_token_times, r.wall_token_times[1:])]
        mesh = self.executor.mesh
        s = reduce_stream(self.stream)
        hw = None
        if s.n_hw_samples:
            n = s.n_hw_samples
            hw = {"n_samples": int(n),
                  "probe_every": int(self.probe.probe_every),
                  "act_bit_sparsity": s.hw_act_bit_sparsity / n,
                  "act_value_sparsity": s.hw_act_value_sparsity / n,
                  "weight_bit_sparsity": s.hw_weight_bit_sparsity / n,
                  "array_utilization": s.hw_array_utilization / n,
                  "cycles": {k: v / n for k, v in s.hw_cycles.items()},
                  "mac_energy_pj": {k: v / n for k, v
                                    in s.hw_mac_energy_pj.items()}}
        return ServeReport(
            results=results,
            prefill_s=s.prefill_s,
            decode_s=s.decode_s,
            steps=s.steps,
            n_syncs=s.n_syncs,
            n_rejected=s.n_rejected,
            total_new_tokens=s.total_new_tokens,
            slot_utilization=s.slot_utilization,
            max_divergence=s.max_divergence,
            deployment=self.engine.deployment_estimate(),
            hw_measured=hw,
            cache_backend=self.serve_cfg.cache_backend,
            n_preemptions=s.n_preemptions,
            prefix_hit_blocks=s.prefix_hit_blocks,
            cow_blocks=s.cow_blocks,
            peak_blocks_in_use=s.peak_blocks_in_use,
            peak_active_slots=s.peak_active_slots,
            mesh_shape=(None if mesh is None
                        else tuple(int(d) for d in mesh.devices.shape)),
            # ladder transitions may null the drafter mid-run; the report
            # names the drafter the run STARTED with
            draft=self.draft_name,
            n_cancelled=s.n_cancelled,
            n_timed_out=s.n_timed_out,
            n_failed=sum(1 for r in results if r.finish_reason == "failed"),
            n_faults=s.n_faults,
            n_injected_faults=s.n_injected_faults,
            n_retries=s.n_retries,
            n_degrades=s.n_degrades,
            n_recoveries=s.n_recoveries,
            drafted_tokens=s.drafted_tokens,
            accepted_tokens=s.accepted_tokens,
            committed_tokens_per_step=s.committed_tokens_per_step,
            ttft_wall=percentiles([ttft_wall(r) for r in self.requests]),
            itl_wall=percentiles(itl),
            # queue-wait and per-class percentiles fold from the stream's
            # ``request`` records (emitted at drain), preserving file/live
            # parity for the SLO numbers too
            queue_wait=percentiles(s.queue_wait_samples),
            slo_classes=self._slo_class_stats(s),
            chunk_tokens=s.chunk_tokens,
        )

    @staticmethod
    def _slo_class_stats(s) -> Optional[Dict[str, dict]]:
        names = sorted(set(s.slo_ttft_samples) | set(s.slo_itl_samples))
        if not names:
            return None
        return {name: {"n": len(s.slo_ttft_samples.get(name, ())),
                       "ttft_wall": percentiles(
                           s.slo_ttft_samples.get(name, ())),
                       "itl_wall": percentiles(
                           s.slo_itl_samples.get(name, ()))}
                for name in names}


class ServingEngine:
    def __init__(self, arch_cfg, params, serve_cfg: Optional[ServeConfig] = None,
                 executor: Optional[Executor] = None,
                 draft_cfg=None, draft_params=None):
        """``draft_cfg``/``draft_params``: a small same-family model for
        ``ServeConfig.draft == "model"`` speculative decoding.  Its traces
        run through an executor built over the SAME mesh as the target's
        (or single-device when none), so drafting composes with
        tensor-parallel serving."""
        self.cfg = arch_cfg
        self.serve_cfg = ServeConfig() if serve_cfg is None else serve_cfg
        if arch_cfg.matmul_mode in ("bp_exact", "bp_approx"):
            # weight-resident fast path: quantize every dense kernel to int8 +
            # per-channel scale ONCE, instead of per-channel re-quantizing the
            # float weights on every forward inside the decode hot loop
            # (idempotent — already-int8 params pass through untouched)
            params = quantize_dense_params(params)
        if executor is None:
            executor = make_executor(arch_cfg, params,
                                     mesh_shape=self.serve_cfg.mesh_shape)
        self.executor = executor
        self.matmul_backend = executor.matmul_backend
        self.draft_cfg = draft_cfg
        self.draft_executor: Optional[Executor] = None
        if draft_cfg is not None:
            if draft_params is None:
                raise ValueError("draft_cfg given without draft_params")
            if draft_cfg.matmul_mode in ("bp_exact", "bp_approx"):
                draft_params = quantize_dense_params(draft_params)
            self.draft_executor = make_executor(draft_cfg, draft_params,
                                                mesh=executor.mesh)
        self._deployment_cache: Dict[int, Optional[dict]] = {}
        self._weight_profile: Optional[dict] = None
        if (self.serve_cfg.probe is not None and self.serve_cfg.probe.enabled
                and arch_cfg.matmul_mode in ("bp_exact", "bp_approx")):
            # probe runs: compute the static weight factor eagerly so the
            # first sampled step folds without a construction-time stall
            self.weight_sparsity_profile()
        # request ids queued for cancellation; the serve loop's sweep
        # drains this set once per iteration (idempotent — unknown or
        # already-finished ids are ignored)
        self._pending_cancels: Set[int] = set()

    def cancel(self, request_id: int) -> None:
        """Request cancellation of an in-flight request.  Applied at the
        serve loop's next lifecycle sweep: the request reaches the
        CANCELLED terminal state, its slot and blocks are freed, and a
        ``cancel`` record lands in the metrics stream.  Safe to call from
        a ``ServeLoop.on_step_end`` hook or before ``serve()`` starts;
        cancelling an unknown or finished request is a no-op."""
        self._pending_cancels.add(int(request_id))

    @property
    def params(self):
        """The executor-placed (pre-quantized) params."""
        return self.executor.params

    def _sample(self, logits, key):
        if self.serve_cfg.temperature <= 0:
            return jnp.argmax(logits, axis=-1)
        return jax.random.categorical(key, logits / self.serve_cfg.temperature,
                                      axis=-1)

    # ------------------------------------------------------------------
    # Static path (device-resident chunked decode)
    # ------------------------------------------------------------------

    def generate(self, batch: dict, key=None, *,
                 max_new_tokens: Optional[int] = None,
                 cache_T: Optional[int] = None) -> GenerationResult:
        """batch: {"tokens": (B, S_prompt) [, "src_embeds", vision...]}.

        ``max_new_tokens``/``cache_T`` override the config per call; pinning
        ``cache_T`` across calls keeps one compiled decode shape (outputs are
        unaffected — the padded cache region is masked)."""
        key = jax.random.PRNGKey(0) if key is None else key
        prompt = batch["tokens"]
        B, S = prompt.shape
        max_new = (self.serve_cfg.max_new_tokens if max_new_tokens is None
                   else max_new_tokens)
        if max_new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if cache_T is None:
            cache_T = S + max_new + self.serve_cfg.cache_margin
        eos = self.serve_cfg.eos_id
        temperature = self.serve_cfg.temperature
        chunk_pref = max(1, self.serve_cfg.decode_chunk)

        t0 = time.perf_counter()
        logits, cache = self.executor.prefill(batch, cache_T)
        logits.block_until_ready()
        t1 = time.perf_counter()

        # device-resident decode: chunks of ``decode_chunk`` tokens advance
        # inside one jitted lax.scan each; per chunk only (B,) tokens + done
        # flags come back to the host (EOS early-exit at chunk boundaries).
        # The cache buffer is donated across chunk dispatches (executor).
        tok = self._sample(logits, key).astype(jnp.int32)
        done = jnp.zeros((B,), bool)
        chunks = [tok[:, None]]
        start, n_steps = 0, max_new - 1
        while start < n_steps:
            if eos is not None and bool(np.asarray(
                    (done | (tok == eos)).all())):
                break
            remaining = n_steps - start
            # tail chunks decompose into powers of two so the number of
            # compiled scan variants stays O(log decode_chunk) no matter how
            # max_new_tokens varies across calls (each distinct chunk length
            # is a separate whole-model compile)
            chunk = (chunk_pref if remaining >= chunk_pref
                     else 1 << (remaining.bit_length() - 1))
            scan = self.executor.decode_scan_fn(chunk, temperature, eos)
            tok, cache, done, key, toks = scan(
                tok, cache, done, key, jnp.int32(S + start), jnp.int32(start))
            chunks.append(toks.T)
            start += chunk
        jax.block_until_ready(tok)
        t2 = time.perf_counter()

        mat = np.concatenate([np.asarray(c) for c in chunks], axis=1)
        if eos is not None:
            # trim to the step the per-token loop would have stopped at:
            # the first column where every row has already emitted EOS
            col_done = (np.cumsum(mat == eos, axis=1) > 0).all(axis=0)
            if col_done.any():
                mat = mat[:, :int(np.argmax(col_done)) + 1]
        return GenerationResult(tokens=mat,
                                prefill_s=t1 - t0, decode_s=t2 - t1,
                                steps=mat.shape[1])

    # ------------------------------------------------------------------
    # Continuous batching (quasi-sync path)
    # ------------------------------------------------------------------

    def _request_key_base(self, req: Request):
        """Per-request PRNG base; the n-th sampled token folds this with n
        (prefill samples with n=0, the decode step folds in the running
        token count — one consistent stream per request)."""
        return jax.random.fold_in(jax.random.PRNGKey(0), req.request_id)

    def _request_key(self, req: Request, n: int):
        return jax.random.fold_in(self._request_key_base(req), n)

    def _finished(self, req: Request, token: int) -> Optional[str]:
        eos = self.serve_cfg.eos_id
        if eos is not None and token == eos:
            return "eos"
        if len(req.tokens) >= req.max_new_tokens:
            return "length"
        return None

    def make_loop(self, requests: Sequence[Request], *, n_slots: int = 8,
                  cache_T: Optional[int] = None,
                  sched_cfg: Optional[SchedulerConfig] = None,
                  extras: Optional[Dict[int, dict]] = None,
                  num_blocks: Optional[int] = None) -> ServeLoop:
        """Build (without running) the orchestration loop for one serve
        call — the unit-testing entry point for its components."""
        return ServeLoop(self, requests, n_slots=n_slots, cache_T=cache_T,
                         sched_cfg=sched_cfg, extras=extras,
                         num_blocks=num_blocks)

    def serve(self, requests: Sequence[Request], *, n_slots: int = 8,
              cache_T: Optional[int] = None,
              sched_cfg: Optional[SchedulerConfig] = None,
              extras: Optional[Dict[int, dict]] = None,
              num_blocks: Optional[int] = None) -> ServeReport:
        """Continuously-batched generation over a request stream.

        ``requests``: ``serving.queue.Request`` objects; ``arrival_time`` is
        interpreted on the decode-step clock (request i becomes visible once
        ``step >= arrival_time``), which makes runs deterministic and
        replayable.  ``extras`` optionally maps request_id -> extra prefill
        inputs (e.g. ``src_embeds`` for the audio family); per-request
        arrays are stacked on a new leading batch axis, so model inputs
        whose batch axis is not leading (the vlm family's M-RoPE
        ``positions``, shaped (3, B, S)) cannot ride through ``extras``.

        The decode cache is backed by ``ServeConfig.cache_backend``:
        ``"slab"`` reserves ``cache_T`` per slot; ``"paged"`` allocates
        ``block_size``-token blocks on demand (``num_blocks`` caps the pool
        — default matches the slab footprint) with automatic prefix sharing
        and LRU-backed preemption-and-requeue when the pool runs dry.
        Greedy outputs are token-identical across backends — and across
        executors (single-device vs mesh).
        """
        return self.make_loop(requests, n_slots=n_slots, cache_T=cache_T,
                              sched_cfg=sched_cfg, extras=extras,
                              num_blocks=num_blocks).run()

    # ------------------------------------------------------------------
    # BitParticle deployment estimate
    # ------------------------------------------------------------------

    def deployment_estimate(self, n_mc: int = 20_000) -> Optional[dict]:
        """Per-layer modeled cycles/energy of the quantized weights on the
        BitParticle array (None unless a bp_* matmul mode is active).
        Cached: it depends only on the immutable params.  Each weight
        slice's bit sparsity is one compiled reduction, so no int32 or
        float32 copy of a slice is built on the device."""
        mode = self.cfg.matmul_mode
        if mode not in ("bp_exact", "bp_approx"):
            return None
        if n_mc in self._deployment_cache:
            return self._deployment_cache[n_mc]
        from repro.core import cost_model as cost
        from repro.core.sparsity import bit_sparsity_fused

        L = self.cfg.num_layers
        per_layer_bs: Dict[int, List[float]] = {}
        for leaf in jax.tree.leaves(self.params):
            if not (hasattr(leaf, "dtype") and leaf.dtype == jnp.int8):
                continue
            if leaf.ndim >= 2 and leaf.shape[0] == L:
                for l in range(L):
                    per_layer_bs.setdefault(l, []).append(
                        float(bit_sparsity_fused(leaf[l])))
            else:
                per_layer_bs.setdefault(-1, []).append(
                    float(bit_sparsity_fused(leaf)))
        if not per_layer_bs:
            return None
        layers = []
        for l in sorted(per_layer_bs):
            bs = float(np.mean(per_layer_bs[l]))
            layers.append({
                "layer": l,          # -1 = non-stacked weights (e.g. lm_head)
                "bit_sparsity": bs,
                "avg_cycles_per_mac": cost.modeled_avg_cycles(mode, bs, n=n_mc),
                "mac_energy_pj": cost.mac_energy_pj(mode, bs),
            })
        mean_bs = float(np.mean([e["bit_sparsity"] for e in layers]))
        est = {
            "mode": mode,
            "per_layer": layers,
            "mean_bit_sparsity": mean_bs,
            "mean_cycles_per_mac": float(
                np.mean([e["avg_cycles_per_mac"] for e in layers])),
            "mean_mac_energy_pj": float(
                np.mean([e["mac_energy_pj"] for e in layers])),
        }
        self._deployment_cache[n_mc] = est
        return est

    def weight_sparsity_profile(self) -> dict:
        """Element-weighted weight bit/value sparsity of the pre-quantized
        int8 params, once per engine (the probe's static factor).  Unlike
        ``deployment_estimate``'s per-kernel mean, these rates weight every
        int8 element equally — the same reduction the probe applies to
        activations, so the two factors are directly comparable."""
        if self._weight_profile is None:
            from repro.serving.probe import per_layer_weight_stats
            stacked, tail = per_layer_weight_stats(self.params,
                                                   self.cfg.num_layers)
            rows = (stacked if tail is None
                    else np.concatenate([stacked, tail[None, :]]))
            total = rows.sum(axis=0)
            n = max(float(total[1]), 1.0)
            per_n = np.maximum(stacked[:, 1], 1.0)
            self._weight_profile = {
                "bit_sparsity": float(total[0] / (7.0 * n)),
                "value_sparsity": float(total[2] / n),
                "per_layer_bit_sparsity":
                    (stacked[:, 0] / (7.0 * per_n)).tolist(),
                "tail_bit_sparsity":
                    (None if tail is None
                     else float(tail[0] / (7.0 * max(tail[1], 1.0)))),
            }
        return self._weight_profile
