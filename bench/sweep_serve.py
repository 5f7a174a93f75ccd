"""Find the serving knee on the chip: one process, one run per offered rate.

    python3 bench/sweep_serve.py <workload> <out.jsonl> <rate> [<rate> ...]

Each rate runs the cell's serve driver for ``--seconds`` (default 20) and
appends its latencies, completions and counters to ``out.jsonl``, with
``kept_up``: the bounded queue never filled (its peak stayed under
``ServerConfig.max_queue``, so backpressure never held the generator back)
and at least 99% of the window's requests were answered inside it. The
knee is the highest rate that kept up; the cell's traffic file takes 0.8 of
it as a number. The benchmark's own runs never run this.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("out")
    ap.add_argument("rates", type=float, nargs="+")
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)

    from pathlib import Path

    from repro.forecast.server import ServerConfig

    from bench import harness

    harness.use_compile_cache()
    harness.device_info(1)
    for i, rate in enumerate(args.rates):
        cell = harness.load_cell(Path(ROOT), args.workload,
                                 seed=5_000_000_000 + i, seconds=args.seconds,
                                 trace=False)
        cell.mix = {**cell.mix, "rate_per_s": rate}
        cell.t_start = time.perf_counter()
        out = harness.drive(cell)
        kept_up = (out.work["queue_peak"] < ServerConfig().max_queue
                   and out.metrics["serve_requests_per_s"] >= 0.99 * rate)
        rec = {"rate_per_s": rate, "kept_up": kept_up, "metrics": out.metrics,
               "numbers": out.numbers, "failed": out.failed,
               "attempted": out.attempted, "counters": out.work,
               "notes": out.notes}
        print(json.dumps(rec), flush=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    main()
