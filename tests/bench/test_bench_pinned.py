"""Granite's readings, pinned: the weights the benchmark draws, the work it
counts for a verify step and a greedy token, and the reference's logits
and gaps.  The numbers were computed by the benchmark code that kept
granite's shapes in ``bench/weights.py``, ``bench/flops.py`` and
``bench/reference.py``, before they moved into ``bench/models/granite.py``;
a cell's readings must not move with where the code lives."""
import hashlib
import json
import os
import sys

import jax
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from bench import check, flops, spec, weights  # noqa: E402


def _config(path):
    with open(path) as f:
        return json.load(f)


SMOKE = _config(os.path.join(HERE, "smoke_config.json"))
GRANITE = _config(os.path.join(ROOT, "bench", "configs",
                               "granite-3-8b.l16.json"))
SEQ = [(7 * i + 3) % 256 for i in range(40)]


def sha(arrays):
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.asarray(a).tobytes())
    return h.hexdigest()


@pytest.fixture(scope="module")
def smoke():
    model = spec.load_model(SMOKE)
    return model, weights.make_params(model, SMOKE)


def test_weights_are_the_same_bits(smoke):
    _, params = smoke
    assert sha(jax.tree_util.tree_leaves(params)) == (
        "71ef6d12175861e19d39ba16a63f24f1bf46d171af420680b6db795779e4cba3")


@pytest.mark.parametrize("contexts, want", [
    ([17, 300, 1023, 1536], {"flops": 241752866816, "bytes": 8644222976}),
    ([1] * 32, {"flops": 1885838770176, "bytes": 8457838592}),
    (list(range(100, 1700, 50)),
     {"flops": 1944491917312, "bytes": 10290749440}),
], ids=["four_rows", "32_fresh_rows", "32_long_rows"])
def test_verify_step_work_is_unchanged(contexts, want):
    run = {"model": spec.load_model(GRANITE), "config": GRANITE}
    assert flops.verify_step(run, contexts) == want


@pytest.mark.parametrize("context, want", [(700, 6961520640),
                                           (1, 6778281984)])
def test_greedy_flops_per_token_are_unchanged(context, want):
    run = {"model": spec.load_model(GRANITE), "config": GRANITE}
    assert flops.greedy_flops_per_token(run, context) == want


@pytest.mark.parametrize("fp8, want", [
    (False,
     "7efc4d1b4e758c18f817dc323ece9e0422f34c7bcc0b962cc2dddb5859d4226e"),
    (True,
     "991faf182dd17ea33541fb62ea1388beede5c60173cc5cf941fbb6c5fb9cf0de"),
], ids=["reference", "float8_control"])
def test_reference_logits_are_the_same_bits(smoke, fp8, want):
    model, params = smoke
    hs = model.hidden_states(params, SMOKE, [SEQ], length=len(SEQ), fp8=fp8)
    logits = model.logits(params, SMOKE, hs[0][:len(SEQ)], fp8=fp8)
    assert logits.dtype == np.float32 and logits.shape == (40, 256)
    assert sha([logits]) == want


def test_served_gaps_are_unchanged(smoke):
    model, params = smoke
    picked = [{"prompt": SEQ[:10], "tokens": SEQ[10:30]},
              {"prompt": SEQ[:4], "tokens": [i * 11 % 256 for i in range(20)]}]
    got = check.served_gaps(model, params, SMOKE, picked,
                            {"max_prompt_len": 16, "max_new_cap": 32},
                            control=True)
    assert got == {"served_logit_gap": 0.7978931069374084,
                   "tokens_compared": 40, "requests_compared": 2,
                   "control_logit_gap": 0.0449998676776886}
