#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` → ``workloads``) names a configuration, a
traffic mix and the chips it needs; ``bench/harness.py`` says what a run
does.  The last line of standard output is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``: each
number that decided ``correct`` beside its limit, also printed as the last
lines of standard error.

Exits 2, printing no result, when JAX finds no TPU or fewer chips than the
cell asks for, or when the benchmark's own files or the program are
missing.
"""
import time

T_PROC0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [CHECKOUT, os.path.join(CHECKOUT, "src")]
# the TPU runtime logs under $TMPDIR, not a fixed path shared by every run
os.environ.setdefault("TPU_LOG_DIR", os.path.join(
    os.environ.get("TMPDIR", "/tmp"), "tpu_logs"))


def _program_in_checkout() -> bool:
    """The served program is this checkout's ``src/repro``, not another
    copy found elsewhere on the path."""
    try:
        import repro
    except ImportError:
        return False
    return all(os.path.abspath(p).startswith(
        os.path.join(CHECKOUT, "src") + os.sep) for p in repro.__path__)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import spec
    try:
        cell = spec.load_cell(args.workload)
    except spec.SpecError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    if not _program_in_checkout():
        print("bench: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    from bench import harness
    try:
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), t_proc0=T_PROC0)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, chk in result["checks"].items():
        print(f"check {name}: {chk['value']} (limit {chk['rule']} "
              f"{chk['limit']})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
