#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 3 ... --seconds 30

For each seed, one run of the cell as ``bench/run.py`` makes it (the
cell's own weights, traffic and load, and a window long enough to finish
its longest requests), and at
the same served positions the widest gap of the program's tokens
(``served_logit_gap``, the lower reading) and of the control's, the
reference rounded to float8 (``control_logit_gap``, the upper reading).
One JSON line per seed on standard output, and to ``--out`` if given.
"""
import time

T_PROC0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                os.path.join(os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))), "src")]


def main(argv=None) -> int:
    from bench import harness, spec

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    for seed in args.seeds:
        t = time.monotonic()
        res = harness.run_cell(cell, seed, args.seconds, False,
                               t_proc0=t, control=True)
        line = json.dumps({
            "workload": cell.name, "seed": seed,
            "served_logit_gap": res["extra"]["program_logit_gap"],
            "control_logit_gap": res["checks"]["served_logit_gap"]["value"],
            "tokens_compared": res["checks"]["tokens_compared"]["value"],
            "correct": res["correct"], "metrics": res["metrics"],
            "extra": res["extra"], "device": res["device"]})
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
