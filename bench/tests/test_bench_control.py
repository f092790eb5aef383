"""The control of the check: the float32 reference computed in int4 in
the program's place reads a gap above the limit that sound runs keep."""

import time

import tiny
from bench import harness


def test_int4_control_fails_the_limit_the_program_keeps(tmp_path,
                                                        monkeypatch):
    root = tiny.make_root(tmp_path)
    tiny.on_cpu(monkeypatch)
    out, sp = harness.run_cell(root, "tiny.small", 31337, 3.0, False,
                               time.perf_counter(), control_bits=4)
    limit = sp["cell"]["check"]["widest_logit_gap_limit"]
    assert out["widest_gap"] <= limit < out["control_gap"]
