"""A second model family is new files only.

A copy of the benchmark gains a test-only Llama family
(``second_family/llama.py``: no multipliers, an untied ``lm_head``), its
configuration, a traffic mix, limits and the entries in
``BENCHMARK.json``; a whole run of the new cell from the copy
(``harness.run_cell`` skipping only the look for a chip, as
``test_bench_rehearsal.py`` does) comes out correct, and no file the copy
already had is edited."""
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, HERE]

from bench import flops, spec  # noqa: E402
from test_bench_spec import snapshot  # noqa: E402

CELL = "llama-smoke.smoke"
# the untied head's logits are ~50x granite's smoke logits, and so are
# bf16's gaps: sound runs read 0-0.031, the float8 control 0.280-0.470
# (CPU, 24 seeds, the control on the same runs)
LIMIT = 0.12

RUN = """
import json, os, sys, time
sys.path[:0] = [os.getcwd(), {src!r}]
from bench import harness, spec
assert os.path.dirname(spec.__file__) == os.path.join(os.getcwd(), "bench")
cell = spec.load_cell({cell!r})
for trace in (False, True):
    res = harness.run_cell(cell, 2**31 + 4099, 2.5, trace,
                           t_proc0=time.monotonic(), require_tpu=False,
                           peaks={{"bf16_flop_per_s": 1e12,
                                  "hbm_bytes_per_s": 1e11}},
                           cache=False, warm_timeout=60.0)
    print(json.dumps({{"model": cell.model.__file__, **res}}))
"""


def copy_benchmark(root):
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)


def add_llama(root):
    """The new files and entries of a Llama cell, in the checkout ``root``."""
    bench_dir = os.path.join(root, "bench")
    shutil.copy(os.path.join(HERE, "second_family", "llama.py"),
                os.path.join(bench_dir, "models", "llama.py"))
    shutil.copy(os.path.join(HERE, "second_family", "llama-smoke.json"),
                os.path.join(bench_dir, "configs", "llama-smoke.json"))
    shutil.copy(os.path.join(HERE, "smoke_traffic.json"),
                os.path.join(bench_dir, "traffic", "smoke.json"))
    with open(os.path.join(bench_dir, "limits", f"{CELL}.json"), "w") as f:
        json.dump({"served_logit_gap": {"limit": LIMIT},
                   "tokens_compared": {"limit": 32}}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "llama-smoke", "source": "tests only",
        "file": "bench/configs/llama-smoke.json", "reduced": [],
        "why": "a second family"})
    bench["workloads"].append({"name": CELL, "config": "llama-smoke",
                               "traffic": "smoke", "chips": 1,
                               "why": "a second family"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)


def test_a_second_family_runs_correct_from_new_files_only(tmp_path):
    copy_benchmark(str(tmp_path))
    before = snapshot(tmp_path / "bench")
    add_llama(str(tmp_path))

    p = subprocess.run(
        [sys.executable, "-c",
         RUN.format(src=os.path.join(ROOT, "src"), cell=CELL)],
        cwd=tmp_path, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    plain, traced = [json.loads(line) for line in
                     p.stdout.strip().splitlines()[-2:]]
    assert plain["model"] == str(tmp_path / "bench" / "models" / "llama.py")
    for res in (plain, traced):
        assert res["correct"], res["checks"]
        assert res["checks"]["tokens_compared"]["value"] >= 32
    assert plain["metrics"]["tokens_per_s"]["value"] > 0
    assert traced["metrics"]["mfu"]["value"] > 0
    after = snapshot(tmp_path / "bench")
    assert all(after[k] == v for k, v in before.items())


def test_the_second_family_counts_its_own_work(tmp_path):
    """The untied head's family counts other bytes than granite's for the
    same sizes: its own verify step, through ``bench.flops``."""
    copy_benchmark(str(tmp_path))
    add_llama(str(tmp_path))
    cell = spec.load_cell(CELL, root=str(tmp_path))
    with open(os.path.join(HERE, "smoke_config.json")) as f:
        smoke = json.load(f)
    granite = {"model": spec.load_model(smoke), "config": smoke}
    llama = {"model": cell.model, "config": cell.config}
    ctxs = [10, 20]
    assert cell.model.layout(cell.config)[1][0] == ("lm_head", "w")
    assert flops.verify_step(llama, ctxs) != flops.verify_step(granite, ctxs)
    assert flops.verify_step(llama, ctxs)["bytes"] > 0


@pytest.mark.parametrize("model_type", ["no_such_family", None])
def test_a_config_without_its_family_is_an_error(tmp_path, model_type):
    copy_benchmark(str(tmp_path))
    add_llama(str(tmp_path))
    path = tmp_path / "bench" / "configs" / "llama-smoke.json"
    config = json.loads(path.read_text())
    if model_type is None:
        del config["model_type"]
    else:
        config["model_type"] = model_type
    path.write_text(json.dumps(config))
    with pytest.raises(spec.SpecError) as e:
        spec.load_cell(CELL, root=str(tmp_path))
    if model_type is not None:
        assert str(tmp_path / "bench" / "models" / f"{model_type}.py") in str(
            e.value)
