"""The readers of the program's own host stamps: front-door ingress, the
serve loop's pickup, SSE egress and the loop's named phases.  Each reads
hand-built records, returns None on records that lack its fields (a
program without the stamps), and reads a value in a whole traced run."""

import json

import pytest

import tiny
from bench import harness, spec

READERS = spec.metric_readers(tiny.REPO)
NAMES = ("frontdoor_ingress_p95_ms", "loop_pickup_p95_ms",
         "sse_egress_p99_ms", "loop_self_ms_per_step")


def _request(i, **kw):
    rec = {"kind": "request", "request_id": i, "queue_wait_s": 0.1,
           "ttft_wall_s": 0.2, "itl_wall_s": [0.05]}
    rec.update(kw)
    return rec


def _requests():
    return [_request(i, ingress_s=0.001 * (i + 1), inbox_s=0.01 * i,
                     hold_s=0.1, egress_s=[0.002 * i, 0.001])
            for i in range(21)]


def _steps():
    # a prefill then two decodes; each record's phases partition the time
    # since the previous one (the first's earlier phases lie before it)
    return [
        {"kind": "prefill", "ts_s": 10.0, "wall_s": 0.5,
         "phases": {"other_s": 2.0, "idle_s": 7.0, "stage_s": 0.01,
                    "dispatch_s": 0.45, "install_s": 0.04}},
        {"kind": "decode", "ts_s": 10.2, "wall_s": 0.18,
         "phases": {"other_s": 0.001, "prepare_s": 0.004, "stage_s": 0.005,
                    "dispatch_s": 0.17, "commit_s": 0.02}},
        {"kind": "decode", "ts_s": 10.4, "wall_s": 0.19,
         "phases": {"other_s": 0.002, "notify_s": 0.001, "inbox_s": 0.001,
                    "sweep_s": 0.001, "plan_s": 0.002, "prepare_s": 0.003,
                    "stage_s": 0.005, "dispatch_s": 0.17, "commit_s": 0.015}},
    ]


@pytest.mark.parametrize("name,want", [
    ("frontdoor_ingress_p95_ms", 20.0),
    ("loop_pickup_p95_ms", 1e3 * (0.1 + 0.19)),
    # 42 samples: 0.000..0.040 by 0.002, and 21 of 0.001
    ("sse_egress_p99_ms", 1e3 * (0.038 + 0.59 * 0.002)),
    # first record's own host time, then every named phase after it
    ("loop_self_ms_per_step",
     1e3 * (0.05 + 0.029 + 0.028) / 2),
])
def test_reader_values(name, want):
    ctx = {"requests": _requests(), "steps": _steps()}
    assert READERS[name](ctx) == pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("name", NAMES)
def test_reader_is_silent_on_records_without_stamps(name):
    """A program without the stamps (the request and step records as they
    were before) reads None, and so does an empty window."""
    old_requests = [{"kind": "request", "request_id": 1,
                     "queue_wait_s": 0.1, "ttft_wall_s": 0.2,
                     "itl_wall_s": []}]
    old_steps = [{"kind": "prefill", "ts_s": 1.0, "wall_s": 0.1,
                  "phases": {"dispatch_s": 0.09, "install_s": 0.01}},
                 {"kind": "decode", "ts_s": 1.2, "wall_s": 0.1,
                  "phases": {"prepare_s": 0.0, "dispatch_s": 0.09,
                             "commit_s": 0.01}}]
    assert READERS[name]({"requests": old_requests,
                          "steps": old_steps}) is None
    assert READERS[name]({"requests": [], "steps": []}) is None


def test_stamps_missing_on_some_requests_are_skipped():
    reqs = [_request(0, ingress_s=None, inbox_s=None, hold_s=None,
                     egress_s=[]),
            _request(1, ingress_s=0.004, inbox_s=0.001, hold_s=0.002,
                     egress_s=[0.003])]
    ctx = {"requests": reqs, "steps": []}
    assert READERS["frontdoor_ingress_p95_ms"](ctx) == pytest.approx(4.0)
    assert READERS["loop_pickup_p95_ms"](ctx) == pytest.approx(3.0)
    assert READERS["sse_egress_p99_ms"](ctx) == pytest.approx(3.0)


def test_traced_run_reads_every_host_stamp_metric(tmp_path, monkeypatch,
                                                  capsys):
    root = tiny.make_root(tmp_path)
    tiny.on_cpu(monkeypatch)
    rc = harness.main(["--workload", "tiny.small", "--seed",
                       str(2**31 + 313), "--seconds", "3", "--trace", "1"],
                      root, 0.0)
    out, _ = capsys.readouterr()
    assert rc == 0
    res = json.loads(out.strip().splitlines()[-1])
    assert res["correct"] is True
    m = res["metrics"]
    for name in NAMES:
        assert m[name]["value"] > 0, name
        assert m[name]["unit"] == "ms"
    # the named phases are part of what the records span between steps
    assert m["loop_self_ms_per_step"]["value"] <= \
        m["host_ms_per_step"]["value"] * (1 + 1e-9)
