"""Percentile, latency and rate arithmetic on synthetic client records."""

import math

import numpy as np
import pytest

import tiny  # noqa: F401  (puts the repo on sys.path)
from bench import stats


def _req(due, first, n, gap, ok=True):
    return {"due": due, "sent": due, "ok": ok,
            "tokens_t": [first + i * gap for i in range(n)] if ok else []}


@pytest.mark.parametrize("q", [0, 50, 90, 95, 99, 100])
def test_percentile_matches_numpy(q):
    xs = np.random.default_rng(0).exponential(1.0, 257)
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    assert stats.percentile([], q) is None


def test_ttft_counts_from_due_time_and_failures_as_missing():
    reqs = [_req(i * 0.1, i * 0.1 + 0.05, 4, 0.01) for i in range(99)]
    m = stats.client_metrics(reqs, 10.0)
    assert m["ttft_p95_ms"] == pytest.approx(50.0)
    assert m["itl_p99_ms"] == pytest.approx(10.0)
    reqs.append(_req(5.0, None, 0, 0.0, ok=False))
    m = stats.client_metrics(reqs, 10.0)
    assert m["n_failed"] == 1 and m["n_due"] == 100
    assert m["ttft_p95_ms"] == pytest.approx(50.0)
    assert stats.percentile([math.inf] * 10 + [1.0] * 90, 95) == math.inf


def test_stall_inside_window_moves_both_tails():
    calm = [_req(i * 0.05, i * 0.05 + 0.03, 20, 0.02) for i in range(180)]
    base = stats.client_metrics(calm, 9.0)
    stalled = []
    for r in calm:
        # the device stalls for 0.5 s at every whole second from 1 to 8:
        # each token arrives late by the stalls that began before it
        t = [x + 0.5 * sum(1 for s in range(1, 9) if x >= s)
             for x in r["tokens_t"]]
        stalled.append(dict(r, tokens_t=t))
    hit = stats.client_metrics(stalled, 9.0)
    assert hit["itl_p99_ms"] > 10 * base["itl_p99_ms"]
    assert hit["ttft_p95_ms"] > 5 * base["ttft_p95_ms"]
    assert hit["tokens_per_s"] < base["tokens_per_s"]


def test_tokens_per_s_counts_only_tokens_inside_the_window():
    reqs = [_req(0.0, 0.5, 10, 0.1), _req(9.5, 9.6, 10, 0.1)]
    # 10 tokens of the first, 4 of the second (9.6 .. 9.9) before t=10
    assert stats.client_metrics(reqs, 10.0)["tokens_per_s"] == \
        pytest.approx(14 / 10.0)
    # requests due after the window add tokens but no latency samples
    reqs.append(_req(10.5, 10.6, 10, 0.1))
    m = stats.client_metrics(reqs, 10.0)
    assert m["n_due"] == 2 and m["n_gaps"] == 18


def test_backlog_and_knee_rule():
    keep = [_req(i * 0.1, i * 0.1 + 0.05, 3, 0.01) for i in range(100)]
    assert stats.sustains(keep, 10.0)
    # service falls behind: the i-th request waits i * 0.05 s
    behind = [_req(i * 0.1, i * 0.1 + i * 0.05, 3, 0.01) for i in range(100)]
    b = stats.backlog_trend(behind, 10.0)
    assert b[-1] > b[1] and not stats.sustains(behind, 10.0)


def test_step_record_reductions():
    steps = [
        {"kind": "prefill", "ts_s": 1.0, "wall_s": 0.1,
         "phases": {"dispatch_s": 0.08}},
        {"kind": "decode", "ts_s": 1.12, "wall_s": 0.02,
         "phases": {"dispatch_s": 0.01}},
        {"kind": "decode", "ts_s": 1.15, "wall_s": 0.02,
         "phases": {"dispatch_s": 0.01}},
    ]
    # span 0.9 .. 1.15 = 0.25 s, dispatch 0.10 s, two decode steps
    assert stats.host_ms_per_step(steps) == pytest.approx(75.0)
    assert stats.prefill_share(steps) == pytest.approx(80.0)
