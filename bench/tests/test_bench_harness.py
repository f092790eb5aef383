"""A whole run at a tiny size on the CPU, past the harness's look for a
chip: result line, metrics, the earlier lines, and ``correct``."""

import json

import tiny
from bench import harness


def run(tmp_path, monkeypatch, capsys, *, trace=0, seed=424242,
        seconds=3, cell=None):
    root = tiny.make_root(tmp_path, cell=cell)
    tiny.on_cpu(monkeypatch)
    rc = harness.main(["--workload", "tiny.small", "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      root, 0.0)
    out, err = capsys.readouterr()
    assert rc == 0
    return json.loads(out.strip().splitlines()[-1]), err


def test_untraced_run_reports_end_to_end_metrics(tmp_path, monkeypatch,
                                                  capsys):
    res, err = run(tmp_path, monkeypatch, capsys, seed=2**31 + 77)
    assert res["correct"] is True
    assert res["attempted"] == 18 and res["failed"] == 0
    assert set(res["metrics"]) == {"ttft_p95_ms", "itl_p99_ms",
                                   "tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert res["checks"]["widest_logit_gap"]["value"] <= \
        res["checks"]["widest_logit_gap"]["limit"]
    assert "compiles_in_window 0" in err
    assert err.strip().splitlines()[-1].startswith("check widest_logit_gap")


def test_traced_run_reports_per_layer_metrics(tmp_path, monkeypatch,
                                              capsys):
    res, _ = run(tmp_path, monkeypatch, capsys, trace=1, seed=99)
    assert res["correct"] is True
    m = res["metrics"]
    for name in ("queue_wait_p95_ms", "host_ms_per_step", "prefill_share",
                 "mfu.decode", "mfu.prefill", "device_idle_share",
                 "ttft_p95_ms.longprompt"):
        assert name in m, name
    assert 0 < m["prefill_share"]["value"] < 100
    assert 0 < m["device_idle_share"]["value"] < 100
    dev = res["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert len(res["breakdown"]["device_ops"]) <= 10
    assert len(res["breakdown"]["idle_gaps"]) <= 10


def test_peak_memory_is_the_fullest_devices():
    class Device:
        def __init__(self, peak):
            self.stats = None if peak is None else {"peak_bytes_in_use": peak}

        def memory_stats(self):
            return self.stats

    assert harness.peak_bytes([Device(7)]) == 7
    assert harness.peak_bytes([Device(3), Device(11), Device(5),
                               Device(None)]) == 11
