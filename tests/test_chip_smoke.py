"""CPU rehearsal of ``chip_smoke.py``: its phase functions, run on the
granite-3-8b smoke config, so the script cannot rot between chip runs.
The TPU requirement stays in ``main()``, which must refuse the CPU."""
import importlib.util
import os

import jax
import pytest

from repro.config import get_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

SIZES = chip_smoke.Sizes(slots=2, requests=4, prompt=(8, 24), new=(4, 12))


def smoke_config(**kw):
    return get_config("granite-3-8b", smoke=True).replace(
        param_dtype="bfloat16", **kw)


def test_main_refuses_cpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out and "needs a TPU" in out.err


def test_one_chip_phases():
    used = chip_smoke.one_chip(smoke_config(), 0, SIZES, compiled=False)
    assert used == [jax.devices()[0]]


def test_four_chip_phases_on_host_mesh():
    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 host devices (tests/conftest.py sets 8)")
    used = chip_smoke.four_chips(smoke_config(), smoke_config(num_layers=3),
                                 0, SIZES)
    assert len(used) == 4


def test_failed_check_raises():
    with pytest.raises(chip_smoke.Failed, match="budget"):
        chip_smoke.check(False, "request 0: 3 tokens, budget 4")
