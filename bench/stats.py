"""Arithmetic the benchmark owns: percentiles, client-side latency metrics
over a window, and reductions over the serve loop's step records.

Kept here, not imported from the program, so that a change to
``repro.serving.telemetry`` cannot move the yardstick.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence


def percentile(samples: Sequence[float], q: float) -> Optional[float]:
    """Linear-interpolated percentile (numpy's default rule); None when
    there is no sample."""
    xs = sorted(float(x) for x in samples)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    if pos == lo or xs[hi] == xs[lo]:
        return xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def in_window(req: dict, window_s: float) -> bool:
    return 0.0 <= req["due"] < window_s


def client_metrics(requests: List[dict], window_s: float) -> Dict[str, float]:
    """End-to-end metrics from the client's records.

    Each record has ``due`` (seconds after the window opened), ``tokens_t``
    (arrival time of each token, same clock) and ``ok``.  TTFT counts every
    request due in the window, from its due time; a request that failed or
    never produced a token counts as missing (infinitely late).  ITL pools
    every gap between consecutive tokens of those requests.  tokens/s
    counts tokens of any request that reached the client inside the
    window."""
    due = [r for r in requests if in_window(r, window_s)]
    ttft = [(r["tokens_t"][0] - r["due"]) if (r["ok"] and r["tokens_t"])
            else math.inf for r in due]
    gaps = [b - a for r in due for a, b in zip(r["tokens_t"],
                                                r["tokens_t"][1:])]
    n_tok = sum(1 for r in requests for t in r["tokens_t"]
                if 0.0 <= t < window_s)
    return {
        "ttft_p95_ms": 1e3 * percentile(ttft, 95) if ttft else math.inf,
        "itl_p99_ms": 1e3 * percentile(gaps, 99) if gaps else math.inf,
        "tokens_per_s": n_tok / window_s,
        "n_due": len(due),
        "n_failed": sum(1 for r in due if not r["ok"]),
        "n_gaps": len(gaps),
    }


def ttft_profile(requests: List[dict], window_s: float) -> Dict[str, object]:
    """Median TTFT and the five longest (ms) of the requests due in the
    window: which few requests set the p95."""
    t = sorted(1e3 * (r["tokens_t"][0] - r["due"]) for r in requests
               if in_window(r, window_s) and r["tokens_t"])
    return {"p50": percentile(t, 50), "worst": [round(x, 1) for x in t[-5:]]}


def lateness_p99_ms(requests: List[dict]) -> Optional[float]:
    late = [r["sent"] - r["due"] for r in requests if r.get("sent") is not None]
    p = percentile(late, 99)
    return None if p is None else 1e3 * p


def backlog_trend(requests: List[dict], window_s: float,
                  n_points: int = 8) -> List[int]:
    """Requests due by t that have no first token by t, sampled at
    ``n_points`` evenly spaced times in the window: a queue that keeps
    growing shows as a rising series."""
    out = []
    for i in range(1, n_points + 1):
        t = window_s * i / n_points
        out.append(sum(1 for r in requests if r["due"] <= t and (
            not r["tokens_t"] or r["tokens_t"][0] > t)))
    return out


def sustains(requests: List[dict], window_s: float) -> bool:
    """The knee rule: every request due in the window finished, and the
    waiting backlog in the last quarter of the window is no larger than
    1.5 times that of the second quarter plus two requests."""
    due = [r for r in requests if in_window(r, window_s)]
    if not due or any(not r["ok"] for r in due):
        return False
    b = backlog_trend(requests, window_s, 8)
    second, last = (b[2] + b[3]) / 2, (b[6] + b[7]) / 2
    return last <= 1.5 * second + 2


# -- step records (the program's own stream) ---------------------------------

def step_span(rec: dict):
    """(start, end) of a step record on the loop's clock."""
    return rec["ts_s"] - rec["wall_s"], rec["ts_s"]


def host_ms_per_step(steps: List[dict]) -> Optional[float]:
    """Host time per decode step: the time the window's step records span,
    less every dispatch inside them, over the number of decode steps."""
    decodes = [s for s in steps if s["kind"] == "decode"]
    if not decodes:
        return None
    spans = [step_span(s) for s in steps]
    total = max(e for _, e in spans) - min(b for b, _ in spans)
    dispatch = sum(s["phases"]["dispatch_s"] for s in steps)
    return 1e3 * (total - dispatch) / len(decodes)


def decode_step_ms(steps: List[dict]) -> Dict[str, Optional[float]]:
    """Median and p90 of the wall time of the window's decode steps."""
    w = [1e3 * s["wall_s"] for s in steps if s["kind"] == "decode"]
    return {"p50": percentile(w, 50), "p90": percentile(w, 90), "n": len(w)}


def prefill_groups(steps: List[dict]) -> Dict[str, float]:
    """The window's prefill steps: how many, how many ran two or more
    prompts, the rows padded to each group's bucket against the prompts'
    own tokens, and the longest step's wall time.  A prefill stalls every
    decoder, so its padding and its longest step show in TTFT and ITL."""
    pre = [s for s in steps if s["kind"] == "prefill"]
    return {
        "n": len(pre),
        "grouped": sum(1 for s in pre if s["group_size"] > 1),
        "padded_tokens": sum(s["group_size"] * s["pad_to"] for s in pre),
        "prompt_tokens": sum(s["prompt_tokens"] for s in pre),
        "wall_max_ms": max((1e3 * s["wall_s"] for s in pre), default=None),
    }


def prefill_share(steps: List[dict]) -> Optional[float]:
    pre = sum(s["phases"]["dispatch_s"] for s in steps
              if s["kind"] == "prefill")
    tot = sum(s["phases"]["dispatch_s"] for s in steps)
    if tot <= 0 or pre <= 0:
        return None
    return 100.0 * pre / tot
