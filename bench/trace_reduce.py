"""Reduce a profiler trace to what the per-layer metrics read.

``load`` turns the ``.xplane.pb`` of one traced window into a plain dict:

    {"ops":   [[name, start_ns, end_ns, desc, device], ...],  # device ops
     "host":  [[name, start_ns, end_ns], ...],                 # host spans
     "marks": {"bench_trace_start": ns, "bench_trace_end": ns},
     "n_devices": planes}

``ops`` are the device's operation events (the ``XLA Ops`` line of each
``/device:TPU:`` plane; on a CPU run, host events that carry an
``hlo_op``), each with the index of its plane among the chips traced
(0 on a CPU run, and where an op carries no index).  Other ``/device:``
planes, such as ``/device:CUSTOM:Megascale Trace``, are not chips.
``desc`` joins the event's string stats, where kernel names appear.
``host`` keeps the spans named in ``HOST_SPANS`` (the program's own
``Tracer`` spans, entered as ``jax.profiler.TraceAnnotation``) and the
two marks the harness writes, which give the traced window and tie the
trace's clock to the host's ``time.perf_counter``.

The rest are pure functions over that dict, checked against a recorded
trace in ``bench/tests/data``.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Tuple

MARKS = ("bench_trace_start", "bench_trace_end")
HOST_SPANS = ("serve", "prefill", "install", "decode", "commit",
              "block_insert", "cow", "verify", "draft") + MARKS


def _desc(ev) -> str:
    parts = []
    for k, v in ev.stats:
        if isinstance(v, str) and k in ("hlo_op", "long_name", "tf_op",
                                        "hlo_module", "name", "kernel_name",
                                        "source"):
            parts.append(v[:160])
    return " ".join(parts)


def chip_plane(name: str) -> bool:
    return name.startswith("/device:TPU:")


def load(profile_dir: str) -> dict:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    pd = ProfileData.from_file(sorted(paths)[-1])
    ops: List[list] = []
    host: List[list] = []
    marks: Dict[str, float] = {}
    device_planes = [p for p in pd.planes if chip_plane(p.name)]
    for i, plane in enumerate(device_planes):
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                ops.append([ev.name, ev.start_ns, ev.end_ns, _desc(ev), i])
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in HOST_SPANS:
                    host.append([ev.name, ev.start_ns, ev.end_ns])
                    if ev.name in MARKS:
                        marks[ev.name] = ev.start_ns
                elif not device_planes:
                    stats = dict(ev.stats)
                    if "hlo_op" in stats:
                        ops.append([ev.name, ev.start_ns, ev.end_ns,
                                    _desc(ev), 0])
    ops.sort(key=lambda o: o[1])
    host.sort(key=lambda h: h[1])
    return {"ops": ops, "host": host, "marks": marks,
            "n_devices": max(len(device_planes), 1)}


def window(trace: dict) -> Tuple[float, float]:
    m = trace["marks"]
    return m["bench_trace_start"], m["bench_trace_end"]


def clip(intervals, a: float, b: float):
    for s, e in intervals:
        s, e = max(s, a), min(e, b)
        if e > s:
            yield s, e


def union_ns(intervals) -> float:
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_ns(trace: dict) -> float:
    """Time in the traced window in which some operation ran on a device,
    each device's own, averaged over the devices traced."""
    a, b = window(trace)
    per_device: Dict[int, list] = {}
    for o in trace["ops"]:
        per_device.setdefault(o[4] if len(o) > 4 else 0, []).append(
            (o[1], o[2]))
    return sum(union_ns(clip(iv, a, b)) for iv in per_device.values()) \
        / trace.get("n_devices", 1)


def idle_share(trace: dict) -> float:
    a, b = window(trace)
    return 1.0 - busy_ns(trace) / (b - a)


def matches(op, kernel: str) -> bool:
    return kernel in op[0] or kernel in op[3]


def kernel_ns(trace: dict, kernel: str,
              spans: List[Tuple[float, float]]) -> float:
    """Summed device time of the ``kernel``'s events that start inside one
    of ``spans`` (trace clock)."""
    spans = sorted(spans)
    total, j = 0.0, 0
    for op in trace["ops"]:
        if not matches(op, kernel):
            continue
        while j < len(spans) and spans[j][1] < op[1]:
            j += 1
        if j < len(spans) and spans[j][0] <= op[1] <= spans[j][1]:
            total += op[2] - op[1]
    return total


CONTAINERS = ("while", "conditional", "call")


def op_kind(name: str) -> str:
    """``%bitparticle_matmul_exact.62 = f32[...] custom-call(...)`` ->
    ``bitparticle_matmul_exact``: the operation's name without its HLO
    text and numeric suffix."""
    base = name.split(" = ", 1)[0].lstrip("%")
    head, _, tail = base.rpartition(".")
    return head if head and tail.isdigit() else base


def top_ops(trace: dict, k: int = 10) -> List[list]:
    """The device operations that took most time in the window, summed by
    kind, in seconds (summed over the devices traced).  Loops and calls,
    which contain other operations, are left out."""
    a, b = window(trace)
    acc: Dict[str, float] = {}
    for op in trace["ops"]:
        kind = op_kind(op[0])
        if kind in CONTAINERS:
            continue
        s, e = max(op[1], a), min(op[2], b)
        if e > s:
            acc[kind] = acc.get(kind, 0.0) + (e - s) * 1e-9
    return [[n, t] for n, t in sorted(acc.items(), key=lambda x: -x[1])[:k]]


def _open_span(host: List[list], t: float) -> str:
    """Innermost program span open at ``t`` (marks excluded)."""
    best: Optional[list] = None
    for h in host:
        if h[0] in MARKS or not (h[1] <= t <= h[2]):
            continue
        if best is None or h[2] - h[1] < best[2] - best[1]:
            best = h
    return best[0] if best is not None else "no span"


def idle_gaps(trace: dict, k: int = 10) -> List[list]:
    """The longest stretches in the window with no operation on any
    device, each named by the host span open at its middle, in seconds."""
    a, b = window(trace)
    ivs = sorted(clip(((o[1], o[2]) for o in trace["ops"]), a, b))
    gaps, cur = [], a
    for s, e in ivs:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if b > cur:
        gaps.append((cur, b))
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[_open_span(trace["host"], (s + e) / 2), (e - s) * 1e-9]
            for s, e in gaps[:k]]


def traced_steps(ctx: dict, kind: str):
    """Step records of ``kind`` that lie wholly inside the traced window,
    each with its interval on the trace's clock.  ``ctx["clock"]`` maps a
    host ``perf_counter`` time to the trace's nanoseconds."""
    trace, to_ns = ctx.get("trace"), ctx.get("clock")
    if trace is None or to_ns is None:
        return []
    a, b = window(trace)
    out = []
    for rec in ctx["steps"]:
        if rec["kind"] != kind:
            continue
        s, e = to_ns(rec["t0"]), to_ns(rec["t1"])
        if a <= s and e <= b:
            out.append((rec, (s, e)))
    return out
