"""The trace reduction on a small recorded trace, and the roofline and
model-operation arithmetic on hand-worked shapes."""

import gzip
import json
import os

import pytest

import tiny
from bench import costs, spec, trace_reduce

DATA = os.path.join(os.path.dirname(__file__), "data", "trace_small.json.gz")
QWEN15 = spec.load_json(os.path.join(tiny.BENCH, "configs",
                                     "qwen2-1.5b.json"))
QWEN7 = spec.load_json(os.path.join(tiny.BENCH, "configs", "qwen2-7b.json"))


@pytest.fixture(scope="module")
def trace():
    with gzip.open(DATA, "rt") as f:
        return json.load(f)


def brute_busy(trace, step_ns=1000):
    """Busy time on a grid of microseconds: the slow, obvious way."""
    import numpy as np
    a, b = trace_reduce.window(trace)
    busy = np.zeros(int((b - a) // step_ns) + 1, bool)
    for o in trace["ops"]:
        s = max(int((o[1] - a) // step_ns), 0)
        e = min(int((o[2] - a) // step_ns), busy.size)
        busy[s:e] = True
    return float(busy.sum()) * step_ns


def test_busy_and_idle_share_match_brute_force(trace):
    a, b = trace_reduce.window(trace)
    busy = trace_reduce.busy_ns(trace)
    assert busy == pytest.approx(brute_busy(trace), rel=0.02, abs=5e3)
    idle = trace_reduce.idle_share(trace)
    assert 0.0 < idle < 1.0
    assert idle == pytest.approx(1 - busy / (b - a))


def test_kernel_time_counts_only_events_inside_the_spans(trace):
    spans = [(h[1], h[2]) for h in trace["host"] if h[0] == "decode"]
    assert spans, "the recorded trace has decode spans"
    inside = trace_reduce.kernel_ns(trace, "bitparticle_matmul", spans)
    every = sum(o[2] - o[1] for o in trace["ops"]
                if trace_reduce.matches(o, "bitparticle_matmul"))
    assert 0 < inside <= every
    assert trace_reduce.kernel_ns(trace, "bitparticle_matmul", []) == 0
    assert trace_reduce.kernel_ns(trace, "no_such_kernel", spans) == 0


def test_breakdown_lists(trace):
    ops = trace_reduce.top_ops(trace)
    assert 0 < len(ops) <= 10
    assert [t for _, t in ops] == sorted((t for _, t in ops), reverse=True)
    gaps = trace_reduce.idle_gaps(trace)
    assert 0 < len(gaps) <= 10
    a, b = trace_reduce.window(trace)
    assert sum(g for _, g in gaps) <= (b - a) * 1e-9 - \
        trace_reduce.busy_ns(trace) * 1e-9 + 1e-9


def test_union_handles_overlap_and_nesting():
    assert trace_reduce.union_ns([(0, 10), (5, 20), (30, 40),
                                  (32, 35)]) == 30


def test_contraction_work_of_qwen2_1_5b():
    # per layer: q 1536x1536, k/v 1536x256, o 1536x1536, ffn 3x 1536x8960
    per_layer = 2 * (1536 * 1536 * 2 + 1536 * 256 * 2 + 3 * 1536 * 8960)
    head = 2 * 1536 * 152064
    assert costs.matmul_ops_per_token(QWEN15, False) == 28 * per_layer
    assert costs.matmul_ops_per_token(QWEN15, True) == 28 * per_layer + head
    work = costs.int8_contractions(QWEN15, 8)
    assert len(work) == 7 * 28          # tied: the head is not int8
    ops, byts = work[4]                  # w_gate, 8 rows
    assert ops == 2 * 8 * 1536 * 8960
    assert byts == 1536 * 8960 + 4 * 8960 + 8 * 1536 + 4 * 8 + 4 * 8 * 8960
    assert len(costs.int8_contractions(QWEN7, 1)) == 7 * 28 + 1


def test_roofline_takes_the_slower_bound():
    # 8 rows of a 3584x18944 int8 matrix: memory-bound on a v5e
    w = costs.int8_contractions(QWEN7, 8)[4:5]
    t = costs.roofline_s(w, 393e12, 819e9)
    assert t == pytest.approx(w[0][1] / 819e9)
    # 4096 rows: compute-bound
    w = costs.int8_contractions(QWEN7, 4096)[4:5]
    assert costs.roofline_s(w, 393e12, 819e9) == pytest.approx(
        w[0][0] / 393e12)
    assert costs.kv_bytes_per_token(QWEN15) == 28672
    assert costs.kv_bytes_per_token(QWEN7) == 57344


def test_roofline_reader_on_the_recorded_trace(trace):
    """The decode roofline reader against a hand computation over the same
    steps: work of each step over kernel time inside it."""
    readers = spec.metric_readers(tiny.REPO)
    spans = [(h[1], h[2]) for h in trace["host"] if h[0] == "decode"]
    steps = [{"kind": "decode", "t0": s * 1e-9, "t1": e * 1e-9,
              "active_slots": 8, "blocks_in_use": 400}
             for s, e in spans]
    peaks = {"int8_ops": 393e12, "bf16_flops": 197e12,
             "hbm_bytes_per_s": 819e9}
    ctx = {"conf": QWEN15, "cell": {"serve": {"block_size": 16}},
           "peaks": peaks, "steps": steps, "requests": [], "trace": trace,
           "clock": lambda s: s * 1e9}
    got = readers["bp_matmul.decode_roofline"](ctx)
    a, b = trace_reduce.window(trace)
    inside = [iv for iv in spans if a <= iv[0] and iv[1] <= b]
    t = trace_reduce.kernel_ns(trace, "bitparticle_matmul", inside) * 1e-9
    need = len(inside) * costs.roofline_s(
        costs.int8_contractions(QWEN15, 8), 393e12, 819e9)
    assert got == pytest.approx(100 * need / t)
    idle = readers["device_idle_share"](ctx)
    assert idle == pytest.approx(100 * trace_reduce.idle_share(trace))


def test_op_kinds_drop_hlo_text_and_suffix(trace):
    assert trace_reduce.op_kind(
        "%bitparticle_matmul_exact.62 = f32[4096,1536] custom-call(") == \
        "bitparticle_matmul_exact"
    assert trace_reduce.op_kind("%while.4 = (s32[]") == "while"
    kinds = [n for n, _ in trace_reduce.top_ops(trace)]
    assert "while" not in kinds
    assert any(k.startswith("paged_attention") for k in kinds)


def test_only_tpu_planes_are_chips():
    assert trace_reduce.chip_plane("/device:TPU:0")
    assert not trace_reduce.chip_plane("/device:CUSTOM:Megascale Trace")
    assert not trace_reduce.chip_plane("/host:CPU")


def _two_plane_trace(second):
    """A 100 ns window; device 0 busy over [10, 60], device 1 over
    ``second``."""
    ops = [["fusion.1", 10, 60, "", 0]]
    ops += [["fusion.2", s, e, "", 1] for s, e in second]
    return {"ops": ops, "host": [], "n_devices": 2,
            "marks": {"bench_trace_start": 0, "bench_trace_end": 100}}


def test_busy_is_each_devices_own_union_averaged():
    one = {"ops": [["fusion.1", 10, 60, ""]], "host": [], "n_devices": 1,
           "marks": {"bench_trace_start": 0, "bench_trace_end": 100}}
    both = _two_plane_trace([(10, 60)])
    assert trace_reduce.busy_ns(both) == trace_reduce.busy_ns(one) == 50
    assert trace_reduce.idle_share(both) == trace_reduce.idle_share(one) \
        == 0.5
    # device 1 busy elsewhere: each device's busy time, averaged
    assert trace_reduce.busy_ns(_two_plane_trace([(60, 100)])) == 45
    assert trace_reduce.busy_ns(_two_plane_trace([])) == 25


def test_mfu_divides_by_the_cell_chips():
    readers = spec.metric_readers(tiny.REPO)
    steps = [{"kind": "decode", "wall_s": 0.07, "active_slots": 12},
             {"kind": "decode", "wall_s": 0.08, "active_slots": 9},
             {"kind": "prefill", "wall_s": 0.2, "prompt_tokens": 3000,
              "group_size": 2}]
    ctx = {"conf": QWEN15, "steps": steps,
           "peaks": {"int8_ops": 393e12}}
    for name in ("mfu.decode", "mfu.prefill"):
        one = readers[name](dict(ctx, chips=1))
        four = readers[name](dict(ctx, chips=4))
        assert one > 0 and four == pytest.approx(one / 4, rel=1e-12)
    ops = costs.matmul_ops_per_token(QWEN15, True) * 21
    assert readers["mfu.decode"](dict(ctx, chips=1)) == pytest.approx(
        100.0 * ops / (0.15 * 393e12), rel=1e-12)
