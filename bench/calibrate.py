"""Read the numbers that decide ``correct`` on the chip, to set their limits.

    python3 bench/calibrate.py <workload> <out.jsonl> [--seeds N] [--seconds S]

In one process (set-up is long): the program as the configuration states it
on N seeds, the control (the configuration's ``control``: the step below its
stated precision) on three, and each fault that the cell can have
(``bench/faults.py``) on three. Each run goes through the
cell's driver as a benchmark run does, with a window of S seconds, and
appends one JSON line of its numbers to ``out.jsonl``. The benchmark's own
runs never run this.
"""

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

FAULTS = {"fit": ["half_batch"], "predict": ["altered_predict_answer"],
          "serve": ["altered_served_answer"]}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("out")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first", type=int, default=0,
                    help="the first of the program's seeds (seed offset)")
    ap.add_argument("--kinds", default="program,control,faults")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)

    from pathlib import Path

    from bench import faults, harness

    harness.use_compile_cache()
    harness.device_info(1)
    kinds = args.kinds.split(",")
    runs = []
    if "program" in kinds:
        runs += [("program", s, None)
                 for s in range(args.first, args.first + args.seeds)]
    if "control" in kinds:
        runs += [("control", 1000 + args.first + s, None) for s in range(3)]
    probe = harness.load_cell(Path(ROOT), args.workload, seed=0,
                              seconds=args.seconds, trace=False)
    for fault in FAULTS[probe.mix["driver"]] if "faults" in kinds else ():
        runs += [(fault, 2000 + args.first + s, fault) for s in range(3)]
    for kind, seed, fault in runs:
        seed = 7_000_000_000 + seed        # above 32 bits, like the driver's
        cell = harness.load_cell(Path(ROOT), args.workload, seed=seed,
                                 seconds=args.seconds, trace=False)
        cell.t_start = time.perf_counter()
        t0 = time.perf_counter()
        with (getattr(faults, fault)() if fault else contextlib.nullcontext()):
            out = harness.drive(cell, control=kind == "control")
        rec = {"workload": args.workload, "kind": kind, "seed": seed,
               "numbers": out.numbers, "detail": out.detail,
               "attempted": out.attempted,
               "failed": out.failed, "metrics": out.metrics,
               "wall_s": time.perf_counter() - t0}
        print(json.dumps(rec), flush=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    main()
