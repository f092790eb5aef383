#!/usr/bin/env python3
"""Find a cell's knee once, on the chip: the highest offered rate at which
completions keep pace with arrivals, with no growing backlog.

    python3 bench/sweep.py --workload <cell> --rates 2,4,6,8 --seconds 51

One process builds and warms the cell once, then runs the open-loop mix
at each rate for ``--seconds`` (same seed, same mix, only the rate
changes), and waits for the loop to drain between rates.  It stops after
two rates in a row that it does not sustain.  Each rate
prints one JSON line; ``sustains`` applies the rule of
``stats.sustains``.  The cell's rate is then 0.8 of the knee, written by
hand into ``bench/workloads/<cell>.json``.
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

from bench import harness, spec, stats, traffic  # noqa: E402


def main(argv) -> int:
    ap = argparse.ArgumentParser(prog="bench/sweep.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sp = spec.cell(ROOT, args.workload)
    shape = harness.mesh_shape(sp)
    harness.enable_compile_cache(ROOT)
    try:
        devices = harness.require_accelerator(int(sp["entry"]["chips"]))
    except harness.NoAccelerator as e:
        harness.log(f"sweep: no accelerator: {e}")
        return harness.EXIT_NO_CHIP
    mesh, cell_devices = harness.cell_mesh(shape, devices)
    params = harness.draw_weights(sp, harness.served_model(ROOT, sp, mesh),
                                  args.seed)
    server = harness.Server(sp, params, trace=False)
    del params
    server.warm(args.seed)
    server.start()
    harness.log(f"sweep: set-up {time.perf_counter() - T_START:.1f} s")
    misses = 0
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            if misses >= 2:
                break
            sched = traffic.schedule(sp["mix"], rate, args.seconds,
                                     args.seed, sp["conf"]["vocab_size"])
            recs, t0, _ = server.window(sched, args.seconds)
            loop = server.loop
            while loop.active or len(loop.rq) or loop.arrivals:
                time.sleep(0.2)
            steps = server.step_records(t0, t0 + args.seconds)
            m = stats.client_metrics(recs, args.seconds)
            ok = stats.sustains(recs, args.seconds)
            misses = 0 if ok else misses + 1
            print(json.dumps({
                "workload": args.workload, "rate_rps": rate,
                "sustains": ok,
                "backlog": stats.backlog_trend(recs, args.seconds),
                "ttft_p95_ms": m["ttft_p95_ms"],
                "itl_p99_ms": m["itl_p99_ms"],
                "tokens_per_s": m["tokens_per_s"], "due": m["n_due"],
                "failed": m["n_failed"],
                "lateness_p99_ms": stats.lateness_p99_ms(recs),
                "decode_step_ms": stats.decode_step_ms(steps),
                "memory_peak_bytes": harness.peak_bytes(cell_devices)}),
                flush=True)
    finally:
        server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
