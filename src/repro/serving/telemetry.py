"""Serving observability: per-step metrics stream, span tracing, reduction.

The serving stack's correctness story is token identity; its *performance*
story is the paper's quasi-synchronous occupancy claim — MAC/slot
utilization under fluctuating per-op work.  Until this module, that claim
was only visible as end-of-run aggregates (``ServeReport``); nobody could
see where one step's time went or whether a commit regressed it.  Three
pieces fix that:

  * :class:`MetricsLogger` — a dependency-free JSONL sink: ONE
    schema-versioned record per prefill / decode / verify step (wall time,
    per-phase durations, committed tokens, acceptance, active slots,
    occupancy/divergence, block-pool gauges, host<->device bytes) plus
    ``preempt`` / ``reject`` lifecycle records.  The stream is the raw
    material for any downstream dashboard — and for the CI regression gate
    (``benchmarks/compare.py``).
  * :class:`Tracer` — Chrome-trace-event JSON ("X" complete events) around
    admission, prefill, draft, verify, commit, preemption, and block-pool
    operations, each on its own thread's ``tid``.  The file loads directly
    in https://ui.perfetto.dev (or ``chrome://tracing``).  With
    ``annotate_device=True`` every span also enters a
    ``jax.profiler.TraceAnnotation`` so host spans line up with device
    traces captured via ``profile_dir``.
  * :class:`PhaseClock` — the serve loop's one timing path for its host
    phases: it stamps every phase, always, and exports it as a span only
    when tracing is on.
  * :func:`reduce_stream` — the PURE reduction from step records to the
    ``ServeReport`` aggregates.  ``ServeLoop.report()`` calls exactly this
    over exactly the records it emitted, so the aggregate counters and the
    metrics stream can never disagree (pinned byte-equal by
    ``tests/test_telemetry.py``).

The :class:`Telemetry` handle bundles the sinks and rides
``ServeConfig.telemetry`` through the engine into the scheduler, cache
managers, block pool, drafters, and executors.  Disabled (the default —
no paths set) it is a strict no-op: spans are a shared null context
manager, ``emit`` writes nothing, and serve() outputs are token-identical
to a run without the handle.  The in-memory step stream lives in the
``ServeLoop`` (not here), so a ``Telemetry`` object can be shared across
serve calls; each run's records append to the same JSONL file.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import threading
import time
from typing import Dict, List, Optional

import numpy as np

SCHEMA_VERSION = 1

#: Required keys per record kind — the golden schema pinned by
#: ``tests/test_telemetry.py``.  Extending a record is fine (consumers
#: must ignore unknown keys); removing or renaming one of these is a
#: breaking change and must bump :data:`SCHEMA_VERSION`.
_STEP_KEYS = {"schema", "kind", "ts_s", "step", "wall_s", "phases",
              "active_slots", "committed_tokens", "h2d_bytes", "d2h_bytes",
              "blocks_in_use", "prefix_hit_blocks", "cow_blocks",
              "peak_blocks_in_use"}
STEP_SCHEMA: Dict[str, set] = {
    "run": {"schema", "kind", "ts_s", "cache_backend", "n_slots", "draft",
            "temperature", "mesh_shape", "block_size"},
    "prefill": _STEP_KEYS | {"group_size", "pad_to", "prompt_tokens",
                             "new_sync"},
    "decode": _STEP_KEYS | {"n_slots", "occupancy", "divergence"},
    # ``chunk_tokens`` (additive): prompt tokens a chunked prefill fed
    # through this verify step (0 on pure speculative steps)
    "verify": _STEP_KEYS | {"n_slots", "occupancy", "divergence",
                            "drafted_tokens", "accepted_tokens",
                            "chunk_tokens"},
    "preempt": {"schema", "kind", "ts_s", "step", "slot", "request_id",
                "discarded_tokens"},
    "reject": {"schema", "kind", "ts_s", "step", "request_id"},
    # robustness records (additive, schema stays v1): lifecycle evictions,
    # fault-injection / fault-detection events, and recovery transitions —
    # see docs/robustness.md for the taxonomy
    "cancel": {"schema", "kind", "ts_s", "step", "request_id", "where"},
    "timeout": {"schema", "kind", "ts_s", "step", "request_id", "where",
                "deadline"},
    "fault": {"schema", "kind", "ts_s", "step", "site"},
    "retry": {"schema", "kind", "ts_s", "step", "site", "attempt"},
    "degrade": {"schema", "kind", "ts_s", "step", "action"},
    "recover": {"schema", "kind", "ts_s", "step", "n_requeued"},
    # hardware-cost observability (additive, schema stays v1): per sampled
    # step the SparsityProbe prices measured activation/weight bit sparsity
    # through the paper's cost models — see docs/observability.md
    "hw_estimate": {"schema", "kind", "ts_s", "step", "phase", "n_layers",
                    "act_bit_sparsity", "act_value_sparsity",
                    "weight_bit_sparsity", "per_layer_act_bit_sparsity",
                    "per_layer_act_value_sparsity", "cycles",
                    "array_utilization", "array_cycles_per_step",
                    "mac_energy_pj"},
    # per-request lifecycle summary (additive, schema stays v1): one record
    # per submitted request, emitted as the loop drains.  ``queue_wait_s``
    # is wall time from queue entry to admission, ``ttft_wall_s`` from
    # queue entry to first token, ``itl_wall_s`` the request's pairwise
    # inter-token gaps — the raw samples behind the report's per-SLO-class
    # percentiles, so the file reduction reproduces them exactly
    # ``ingress_s`` / ``inbox_s`` / ``hold_s`` (additive) split the time
    # before queue entry: front door read the head -> loop inbox -> drained
    # into the arrivals -> waiting queue; ``egress_s`` is, per token written
    # to the client, write done - commit (None / [] where a stamp is
    # missing, as for requests that never crossed the front door)
    "request": {"schema", "kind", "ts_s", "step", "request_id", "slo_class",
                "finish_reason", "n_tokens", "queue_wait_s", "ttft_wall_s",
                "itl_wall_s", "ingress_s", "inbox_s", "hold_s", "egress_s"},
}

#: The process's one clock origin (``time.perf_counter`` seconds).  A step
#: record's ``ts_s`` and a Chrome-trace event's ``ts`` (in µs) count from
#: it, so they and the ``perf_counter`` instant of any profiler
#: ``TraceAnnotation`` convert to one another with one offset.
CLOCK_ORIGIN = time.perf_counter()


def percentiles(samples, qs=(50, 90, 99)) -> Optional[Dict[str, float]]:
    """{p50, p90, p99} (or custom ``qs``) of a sample set, or None when no
    sample exists.  THE percentile rule for the whole repo: the engine's
    ttft/itl wall-clock report fields and every benchmark summary go
    through this one helper instead of hand-rolling the math."""
    xs = np.asarray([s for s in samples if s is not None], np.float64)
    if xs.size == 0:
        return None
    return {f"p{q}": float(np.percentile(xs, q)) for q in qs}


class _NullSpan:
    """Shared do-nothing context manager: the disabled-telemetry span.
    Identity-pinned by tests — the hot loop must not allocate per span
    when tracing is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


class MetricsLogger:
    """Append-only JSONL sink: one line per record, flushed per write so a
    crashed run still leaves a readable stream.  Dependency-free by
    design (the ROADMAP's 'wandblog in spirit, local JSONL sink').

    Also a context manager: ``with MetricsLogger(p) as m: ...`` flushes
    and closes on exit — including on an exception mid-serve, so a crash
    never truncates the stream mid-record (each ``log`` writes one full
    line and flushes before returning)."""

    def __init__(self, path: str):
        self.path = path
        d = os.path.dirname(os.path.abspath(path))
        if d:
            os.makedirs(d, exist_ok=True)
        self._f = open(path, "a", encoding="utf-8")

    def log(self, record: dict):
        self._f.write(json.dumps(record, default=float) + "\n")
        self._f.flush()

    def close(self):
        if not self._f.closed:
            self._f.flush()
            self._f.close()

    def __enter__(self) -> "MetricsLogger":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False


class Tracer:
    """Chrome-trace-event recorder (complete "X" events, µs timestamps on
    :data:`CLOCK_ORIGIN`).

    Each event carries its thread's ``tid`` (with a ``thread_name``
    metadata event the first time a thread records), so spans nest per
    thread: the serve loop's worker and the front door's asyncio thread
    each get a track.  ``write()`` dumps the standard
    ``{"traceEvents": [...]}`` wrapper that perfetto / chrome://tracing
    load directly.

    ``keep_events=False`` keeps no event list (nothing would write it):
    spans then only enter ``jax.profiler.TraceAnnotation`` when
    ``annotate_device`` is on, and ``write`` has nothing to dump.
    """

    def __init__(self, *, annotate_device: bool = False,
                 keep_events: bool = True):
        self.events: Optional[List[dict]] = [] if keep_events else None
        self._pid = os.getpid()
        self._annotation = None
        if annotate_device:
            try:
                import jax
                self._annotation = jax.profiler.TraceAnnotation
            except ImportError:   # profiler unavailable: host spans work
                pass
        self._tids: set = set()
        self._tid_lock = threading.Lock()
        if self.events is not None:
            self.events.append({
                "name": "process_name", "ph": "M", "pid": self._pid,
                "tid": 0, "args": {"name": "repro.serving"},
            })

    @staticmethod
    def _us(t: float) -> float:
        return (t - CLOCK_ORIGIN) * 1e6

    def _tid(self) -> int:
        tid = threading.get_native_id()
        if tid not in self._tids:
            with self._tid_lock:
                if tid not in self._tids:
                    self._tids.add(tid)
                    self.events.append({
                        "name": "thread_name", "ph": "M", "pid": self._pid,
                        "tid": tid,
                        "args": {"name": threading.current_thread().name},
                    })
        return tid

    def annotate(self, name: str):
        """Enter a profiler annotation named ``name`` (None when device
        annotation is off); the caller exits it."""
        if self._annotation is None:
            return None
        ann = self._annotation(name)
        ann.__enter__()
        return ann

    def complete(self, name: str, t0: float, t1: float,
                 cat: str = "serving", **args):
        """Record one span from ``perf_counter`` stamps already taken, on
        the calling thread's track."""
        if self.events is None:
            return
        self.events.append({
            "name": name, "cat": cat, "ph": "X", "ts": self._us(t0),
            "dur": (t1 - t0) * 1e6, "pid": self._pid, "tid": self._tid(),
            "args": {k: _jsonable(v) for k, v in args.items()},
        })

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "serving", **args):
        ann = self.annotate(name)
        t0 = time.perf_counter()
        try:
            yield self
        finally:
            t1 = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.complete(name, t0, t1, cat, **args)

    def instant(self, name: str, cat: str = "serving", **args):
        if self.events is None:
            return
        self.events.append({
            "name": name, "cat": cat, "ph": "i", "s": "t",
            "ts": self._us(time.perf_counter()), "pid": self._pid,
            "tid": self._tid(),
            "args": {k: _jsonable(v) for k, v in args.items()},
        })

    def counter(self, name: str, **values):
        """Chrome-trace counter ("C") sample: perfetto renders one stacked
        counter track named ``name`` with a series per kwarg."""
        if self.events is None:
            return
        self.events.append({
            "name": name, "ph": "C", "ts": self._us(time.perf_counter()),
            "pid": self._pid, "tid": self._tid(),
            "args": {k: _jsonable(v) for k, v in values.items()},
        })

    def write(self, path: str):
        if self.events is None:
            return
        d = os.path.dirname(os.path.abspath(path))
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w", encoding="utf-8") as f:
            # a copy: another thread (the front door) may still append
            json.dump({"traceEvents": list(self.events),
                       "displayTimeUnit": "ms"}, f, default=float)


class Phase:
    """One timed host phase of the serve loop (see :class:`PhaseClock`).
    After the ``with`` block, ``t0`` / ``t1`` hold its ``perf_counter``
    stamps."""

    __slots__ = ("_clock", "name", "key", "_args", "_ann", "t0", "t1")

    def __init__(self, clock: "PhaseClock", name: str, key: str,
                 args: dict):
        self._clock = clock
        self.name = name
        self.key = key
        self._args = args
        self._ann = None
        self.t0 = self.t1 = 0.0

    def __enter__(self) -> "Phase":
        clock = self._clock
        tracer = clock.tel.tracer
        if tracer is not None:
            self._ann = tracer.annotate(self.name)
        self.t0 = t0 = time.perf_counter()
        clock.add(OTHER_PHASE, t0 - clock.mark)
        return self

    def __exit__(self, *exc) -> bool:
        clock = self._clock
        self.t1 = clock.mark = t1 = time.perf_counter()
        clock.add(self.key, t1 - self.t0)
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        tracer = clock.tel.tracer
        if tracer is not None:
            tracer.complete(self.name, self.t0, t1, **self._args)
        return False


#: the ``phases`` key of host time spent outside every named phase
#: (record emission, recovery, the few statements between phases)
OTHER_PHASE = "other_s"


class PhaseClock:
    """The serve loop's one timing path for its host phases.

    ``with clock("decode.stage", "stage_s"):`` stamps the block with
    ``perf_counter``, always, and adds its duration under ``"stage_s"``
    to what the next step record takes (:meth:`take`).  Only when tracing
    is on does the phase also become a span (a Chrome-trace event and, with
    ``annotate_device``, a profiler annotation) named ``"decode.stage"``,
    from the same two stamps.  Time between phases goes to
    :data:`OTHER_PHASE`, so the durations one record takes partition the
    time since the previous record's stamp exactly.  Phases do not nest;
    the clock is used by one thread, the loop's."""

    def __init__(self, tel: "Telemetry"):
        self.tel = tel
        self.acc: Dict[str, float] = {}
        self.mark = time.perf_counter()   # end of the last phase or take

    def __call__(self, name: str, key: str, **args) -> Phase:
        return Phase(self, name, key, args)

    def add(self, key: str, seconds: float) -> None:
        self.acc[key] = self.acc.get(key, 0.0) + seconds

    def take(self, now: float, *pinned: str) -> Dict[str, float]:
        """The durations accumulated since the last call, up to ``now``
        (a ``perf_counter`` stamp, the record's own), with every ``pinned``
        key present (0.0 where its phase did not run)."""
        self.add(OTHER_PHASE, now - self.mark)
        self.mark = now
        out = dict.fromkeys(pinned, 0.0)
        out.update(self.acc)
        self.acc = {}
        return out


def _jsonable(v):
    if isinstance(v, (np.integer,)):
        return int(v)
    if isinstance(v, (np.floating,)):
        return float(v)
    return v


class Telemetry:
    """The serving observability handle: metrics + trace + profiler sinks.

    Construct with no arguments for the disabled no-op handle (what the
    engine builds when ``ServeConfig.telemetry`` is None).  ``span`` /
    ``instant`` / ``emit`` are safe to call unconditionally — disabled
    they cost a dict lookup, not an allocation.  ``counters`` tracks
    cumulative host<->device byte movement (the loop snapshots deltas per
    step record); counting stays on even when sinks are off so the step
    stream is identical either way.
    """

    def __init__(self, metrics_path: Optional[str] = None,
                 trace_path: Optional[str] = None, *,
                 profile_dir: Optional[str] = None,
                 annotate_device: bool = False):
        self.metrics = MetricsLogger(metrics_path) if metrics_path else None
        self.trace_path = trace_path
        self.tracer = (Tracer(annotate_device=annotate_device,
                              keep_events=bool(trace_path))
                       if (trace_path or annotate_device) else None)
        self.profile_dir = profile_dir
        self.counters: Dict[str, int] = {"h2d_bytes": 0, "d2h_bytes": 0}
        self._profiling = False

    @property
    def enabled(self) -> bool:
        return (self.metrics is not None or self.tracer is not None
                or self.profile_dir is not None)

    # -- sinks ---------------------------------------------------------------

    def span(self, name: str, **args):
        if self.tracer is None:
            return NULL_SPAN
        return self.tracer.span(name, **args)

    def record_span(self, name: str, t0: float, t1: float, **args):
        """A span from ``perf_counter`` stamps taken earlier, for work that
        ran before it was known which handle records it (the front door
        parses and routes a request before it knows the replica).  It
        reaches the Chrome trace only: a profiler annotation cannot be
        entered after the fact."""
        if self.tracer is not None:
            self.tracer.complete(name, t0, t1, **args)

    def instant(self, name: str, **args):
        if self.tracer is not None:
            self.tracer.instant(name, **args)

    def counter(self, name: str, **values):
        if self.tracer is not None:
            self.tracer.counter(name, **values)

    def emit(self, record: dict):
        if self.metrics is not None:
            self.metrics.log(record)

    def count(self, key: str, n) -> None:
        self.counters[key] = self.counters.get(key, 0) + int(n)

    # -- device profiler hooks ----------------------------------------------

    def start_profile(self):
        """Start a ``jax.profiler`` trace into ``profile_dir`` (no-op when
        unset or the profiler is unavailable)."""
        if self.profile_dir is None or self._profiling:
            return
        try:
            import jax
            jax.profiler.start_trace(self.profile_dir)
            self._profiling = True
        except Exception:
            self._profiling = False

    def stop_profile(self):
        if not self._profiling:
            return
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception:
            pass
        self._profiling = False

    def flush(self):
        if self.tracer is not None and self.trace_path:
            self.tracer.write(self.trace_path)

    def close(self):
        self.stop_profile()
        self.flush()
        if self.metrics is not None:
            self.metrics.close()

    def __enter__(self) -> "Telemetry":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False


#: Shared disabled handle for components constructed without one (direct
#: cache-manager / executor construction in tests).  Its counters are a
#: write-only sink nothing reads.
NULL_TELEMETRY = Telemetry()


# ---------------------------------------------------------------------------
# Stream reduction: step records -> ServeReport aggregates
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class StreamSummary:
    """Aggregates of one serve call's step-record stream.  Every field maps
    1:1 onto a ``ServeReport`` counter; ``ServeLoop.report()`` is a pure
    function of this object plus the per-request results."""

    prefill_s: float = 0.0            # sum of prefill dispatch walls
    decode_s: float = 0.0             # sum of decode/verify dispatch walls
    steps: int = 0                    # decode + verify records
    n_syncs: int = 0                  # prefill records opening a sync
    total_new_tokens: int = 0         # emitted - discarded-at-preemption
    committed_decode_tokens: int = 0  # decode/verify commits only
    slot_utilization: float = 0.0
    committed_tokens_per_step: float = 0.0
    max_divergence: int = 0
    n_preemptions: int = 0
    n_rejected: int = 0
    drafted_tokens: int = 0
    accepted_tokens: int = 0
    peak_active_slots: int = 0
    prefix_hit_blocks: int = 0
    cow_blocks: int = 0
    peak_blocks_in_use: int = 0
    h2d_bytes: int = 0
    d2h_bytes: int = 0
    # robustness counters (cancel / timeout / fault / retry / degrade /
    # recover records)
    n_cancelled: int = 0
    n_timed_out: int = 0
    n_faults: int = 0                 # fault records (injected + detected)
    n_injected_faults: int = 0        # fault records with injected=True
    n_retries: int = 0
    n_degrades: int = 0
    n_recoveries: int = 0
    # hw_estimate records (sparsity-probe samples): order-preserving sums;
    # the report divides by n_hw_samples for the measured-traffic means
    n_hw_samples: int = 0
    hw_act_bit_sparsity: float = 0.0
    hw_act_value_sparsity: float = 0.0
    hw_weight_bit_sparsity: float = 0.0
    hw_array_utilization: float = 0.0
    hw_cycles: Dict[str, float] = dataclasses.field(default_factory=dict)
    hw_mac_energy_pj: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    # chunked prefill: prompt tokens ingested through multi-token chunk
    # steps (distinct from committed/generated tokens)
    chunk_tokens: int = 0
    # per-request lifecycle records: the wall-clock samples behind the
    # report's queue-wait and per-SLO-class latency percentiles (floats
    # round-trip JSON exactly, so file and live reductions agree)
    n_requests: int = 0
    queue_wait_samples: List[float] = dataclasses.field(default_factory=list)
    slo_ttft_samples: Dict[str, List[float]] = dataclasses.field(
        default_factory=dict)
    slo_itl_samples: Dict[str, List[float]] = dataclasses.field(
        default_factory=dict)


def reduce_stream(records) -> StreamSummary:
    """Fold a step-record stream (dicts, in emission order) into the
    ``ServeReport`` aggregates.  Accepts both live records and records
    parsed back from the JSONL file — the float math is order-preserving
    sums of the recorded values, so the two reductions are byte-equal
    (JSON round-trips binary64 exactly)."""
    s = StreamSummary()
    occupancy_sum = 0.0
    emitted = 0
    discarded = 0
    for r in records:
        kind = r.get("kind")
        if kind == "prefill":
            s.prefill_s += r["phases"]["dispatch_s"]
            if r["new_sync"]:
                s.n_syncs += 1
            emitted += r["committed_tokens"]
        elif kind in ("decode", "verify"):
            s.steps += 1
            s.decode_s += r["phases"]["dispatch_s"]
            occupancy_sum += r["occupancy"]
            s.committed_decode_tokens += r["committed_tokens"]
            emitted += r["committed_tokens"]
            s.max_divergence = max(s.max_divergence, int(r["divergence"]))
            s.peak_active_slots = max(s.peak_active_slots,
                                      int(r["active_slots"]))
            if kind == "verify":
                s.drafted_tokens += int(r["drafted_tokens"])
                s.accepted_tokens += int(r["accepted_tokens"])
                s.chunk_tokens += int(r.get("chunk_tokens", 0))
        elif kind == "preempt":
            s.n_preemptions += 1
            discarded += int(r["discarded_tokens"])
            continue
        elif kind == "reject":
            s.n_rejected += 1
            continue
        elif kind == "cancel":
            s.n_cancelled += 1
            continue
        elif kind == "timeout":
            s.n_timed_out += 1
            continue
        elif kind == "fault":
            s.n_faults += 1
            if r.get("injected"):
                s.n_injected_faults += 1
            continue
        elif kind == "retry":
            s.n_retries += 1
            continue
        elif kind == "degrade":
            s.n_degrades += 1
            continue
        elif kind == "recover":
            s.n_recoveries += 1
            continue
        elif kind == "request":
            s.n_requests += 1
            cls = str(r["slo_class"])
            if r["queue_wait_s"] is not None:
                s.queue_wait_samples.append(float(r["queue_wait_s"]))
            if r["ttft_wall_s"] is not None:
                s.slo_ttft_samples.setdefault(cls, []).append(
                    float(r["ttft_wall_s"]))
            if r["itl_wall_s"]:
                s.slo_itl_samples.setdefault(cls, []).extend(
                    float(v) for v in r["itl_wall_s"])
            continue
        elif kind == "hw_estimate":
            s.n_hw_samples += 1
            s.hw_act_bit_sparsity += r["act_bit_sparsity"]
            s.hw_act_value_sparsity += r["act_value_sparsity"]
            s.hw_weight_bit_sparsity += r["weight_bit_sparsity"]
            s.hw_array_utilization += r["array_utilization"]
            for k, v in r["cycles"].items():
                s.hw_cycles[k] = s.hw_cycles.get(k, 0.0) + v
            for k, v in r["mac_energy_pj"].items():
                s.hw_mac_energy_pj[k] = s.hw_mac_energy_pj.get(k, 0.0) + v
            continue
        else:
            continue
        # pool gauges are cumulative snapshots; max == final (monotone)
        s.prefix_hit_blocks = max(s.prefix_hit_blocks,
                                  int(r["prefix_hit_blocks"]))
        s.cow_blocks = max(s.cow_blocks, int(r["cow_blocks"]))
        s.peak_blocks_in_use = max(s.peak_blocks_in_use,
                                   int(r["peak_blocks_in_use"]))
        s.h2d_bytes += int(r["h2d_bytes"])
        s.d2h_bytes += int(r["d2h_bytes"])
    s.total_new_tokens = emitted - discarded
    if s.steps:
        s.slot_utilization = occupancy_sum / s.steps
        s.committed_tokens_per_step = s.committed_decode_tokens / s.steps
    return s


def read_jsonl(path: str) -> List[dict]:
    """Parse a metrics JSONL file back into the record stream."""
    out = []
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out
