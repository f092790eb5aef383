"""The one traffic generator: turns a mix's data file into a schedule.

A mix (``bench/traffic/<name>.json``) gives length distributions and an
arrival process; the cell (``bench/workloads/<name>.json``) gives the
rate.  The arrival times and the lengths are drawn once, from the mix's
``base_seed``; the run's seed draws the prompt tokens (and, in the
harness, the weights).  So every seed asks for the same work at the same
moments: with a few dozen requests in a window, a tail such as a p95 of
TTFT is set by the few worst bursts, and seeds that reordered the
arrivals would change the work, not only the inputs.  Exactly
``round(rate * window_s)`` requests fall due in the window.  A
tail of further requests, due after the window closes, keeps the load on
while the window's requests finish; they are not counted.
"""

from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

TAIL_S = 60.0     # seconds of load scheduled past the window


def _lengths(rng, spec: dict, n: int) -> np.ndarray:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = np.exp(rng.normal(math.log(spec["median"]), spec["sigma"], n))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _gaps(rng, spec: dict, rate: float, n: int) -> np.ndarray:
    if spec["process"] != "poisson":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    return rng.exponential(1.0 / rate, n)


def max_lengths(mix: dict) -> Dict[str, int]:
    return {"prompt": int(mix["prompt_len"]["max"]),
            "output": int(mix["output_len"]["max"])}


def schedule(mix: dict, rate: float, window_s: float, seed: int,
             vocab: int, tail_s: float = TAIL_S) -> List[dict]:
    """Requests ``{"id", "due", "prompt", "max_new_tokens"}`` sorted by due
    time (seconds after the window opens)."""
    n_w = max(1, int(round(rate * window_s)))
    n_t = int(math.ceil(rate * tail_s))
    base = np.random.default_rng(int(mix.get("base_seed", 0)))
    gaps_w = _gaps(base, mix["arrivals"], rate, n_w - 1)
    if n_w > 1:
        # the window's n_w arrivals span (n_w - 1) / n_w of the window
        gaps_w *= window_s * (n_w - 1) / n_w / gaps_w.sum()
    gaps_t = _gaps(base, mix["arrivals"], rate, n_t)
    prompts = [_lengths(base, mix["prompt_len"], n) for n in (n_w, n_t)]
    outputs = [_lengths(base, mix["output_len"], n) for n in (n_w, n_t)]

    due_w = np.concatenate([[0.0], np.cumsum(gaps_w)])
    due_t = (window_s + gaps_t[0] / 2 + np.concatenate(
        [[0.0], np.cumsum(gaps_t[1:])])) if n_t else np.zeros(0)
    due = np.concatenate([due_w, due_t])
    plen = np.concatenate(prompts)
    olen = np.concatenate(outputs)
    rng = np.random.default_rng(int(seed))
    out = []
    for i in range(n_w + n_t):
        out.append({"id": i, "due": float(due[i]),
                    "prompt": rng.integers(2, vocab, int(plen[i])).tolist(),
                    "max_new_tokens": int(olen[i])})
    return out
