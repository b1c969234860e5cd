#!/usr/bin/env python3
"""Find the highest rate an open-loop cell sustains (its knee), in one
process.

    python3 bench/sweep.py --workload <cell> --rates 4 6 8 --seconds 30

For each rate, one run of the cell as ``bench/run.py`` makes it, with the
traffic's ``rate_per_s`` replaced.  A rate is sustained when the queue is
no longer at the window's end than at its start and no request fails,
is cut short or stalls.  One JSON line per rate on standard output (and
appended to ``--out``): the rate, the queue at the window's start and end,
the requests' account, tokens/s, p90 time to first token and p90 time per
output token.
"""
import time

T_PROC0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [CHECKOUT, os.path.join(CHECKOUT, "src")]
os.environ.setdefault("TPU_LOG_DIR", os.path.join(
    os.environ.get("TMPDIR", "/tmp"), "tpu_logs"))


def main(argv=None) -> int:
    from bench import harness, spec

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=2**31 + 1)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    if cell.traffic["loop"] != "open":
        raise SystemExit(f"{cell.name}: a sweep needs open-loop traffic")

    seen = {}
    serve = harness.serve

    async def watched(frontend, mix, specs, seconds, trace_dir, marks,
                      warm_timeout):
        records = await serve(frontend, mix, specs, seconds, trace_dir, marks,
                              warm_timeout)
        t0, t1 = marks["t0"], marks["t1"]
        ttft = harness.ttft_samples(records, t0, t1)
        seen.update(queue_start=marks["counters0"]["queue_depth"],
                    queue_end=marks["counters1"]["queue_depth"],
                    ttft_p90_s=harness._pct(ttft, 90))
        return records

    harness.serve = watched
    for rate in args.rates:
        seen.clear()
        mix = dict(cell.traffic, rate_per_s=rate)
        res = harness.run_cell(cell._replace(traffic=mix), args.seed,
                               args.seconds, False, t_proc0=time.monotonic())
        req = res["extra"]["requests"]
        line = {"workload": cell.name, "rate_per_s": rate, **seen,
                "sustained": (seen["queue_end"] <= seen["queue_start"]
                              and req["failed"] == req["short"]
                              == req["stalled"] == 0),
                "requests": req, "correct": res["correct"],
                "metrics": {k: v["value"] for k, v in res["metrics"].items()},
                "device": res["device"]}
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
