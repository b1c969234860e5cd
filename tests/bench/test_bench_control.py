"""The control at a size a test run can hold: the reference rounded to
float8, put in the program's place, reads the served positions of a
smoke-size run and must come out not correct through the run's own
``checks``, on three seeds, while the program's own tokens read within the
limit.  (On the chip the same comparison runs at the
cell's own size: ``bench/calibrate.py``.)"""
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), HERE]

from bench import harness  # noqa: E402
from test_bench_rehearsal import LIMIT, PEAKS, smoke_cell  # noqa: E402


@pytest.mark.parametrize("seed", [3_000_000_011, 3_000_000_012,
                                  3_000_000_013])
def test_control_fails_where_the_program_passes(seed):
    res = harness.run_cell(smoke_cell("closed"), seed, 2.0, False,
                           t_proc0=time.monotonic(), require_tpu=False,
                           peaks=PEAKS, cache=False, control=True)
    assert not res["correct"], res["checks"]
    assert res["checks"]["served_logit_gap"]["value"] > LIMIT
    assert res["extra"]["program_logit_gap"] <= LIMIT
