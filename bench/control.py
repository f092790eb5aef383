#!/usr/bin/env python3
"""Read the control of a cell's check on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 15

For each seed, one process runs the cell's window at its own load and
size, then reads both numbers over the same sample of served requests:
the program's widest logit gap, and the control's: at each of those
positions, the gap of the token that the int4 reference puts first.  The
limit in ``bench/workloads/<cell>.json`` must lie between the two.  The
benchmark's own runs never run the control.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:] = [ROOT, os.path.join(ROOT, "src")] + [
    p for p in sys.path if os.path.abspath(p or ".") != HERE]

from bench import harness  # noqa: E402

CONTROL_BITS = 4


def main(argv) -> int:
    ap = argparse.ArgumentParser(prog="bench/control.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        try:
            out, sp = harness.run_cell(ROOT, args.workload, seed,
                                       args.seconds, False, t,
                                       control_bits=CONTROL_BITS)
        except harness.NoAccelerator as e:
            harness.log(f"control: no accelerator: {e}")
            return harness.EXIT_NO_CHIP
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "program_widest_gap": out["widest_gap"],
            "control_widest_gap": out["control_gap"],
            "failed": out["e2e"]["n_failed"],
            "limit": sp["cell"]["check"]["widest_logit_gap_limit"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
