"""Cells and metrics are found by name: adding a configuration, a traffic
mix or a metric reader, plus entries in BENCHMARK.json, adds a cell or a
metric without editing any file that is already there."""
import json
import os
import re
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import spec  # noqa: E402


def snapshot(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = fh.read()
    return out


def test_every_cell_of_the_benchmark_loads():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.chips == w["chips"]
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert cell.per_layer
        moved = {m["moves"] for m in cell.per_layer}
        assert moved <= {m["name"] for m in cell.end_to_end}
    for m in bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_benchmark_file_keeps_its_shape():
    """Names, units, keys, bounds and the metric arrows of BENCHMARK.json."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert set(bench) == KEYS["top"]
    assert 1 <= bench["run_seconds"] <= 51
    for p in bench["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p)) and ".." not in p
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names))
        for e in bench[group]:
            extra = {"workloads"} if group in ("end_to_end",
                                               "per_layer") else set()
            assert KEYS[group] <= set(e) <= KEYS[group] | extra, e
            assert NAME.match(e["name"]), e["name"]
            for key in ("why", "layer", "source"):
                if key in e:
                    assert _line(e[key]), e
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in (
                    "lower", "higher")
    for c in bench["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        assert all(NAME.match(k) for k in c["reduced"])
    cells = {w["name"]: w for w in bench["workloads"]}
    for w in cells.values():
        assert w["chips"] in (1, 4) and NAME.match(w["traffic"])
    assert 2 * sum(w["chips"] == 4 for w in cells.values()) <= max(
        2, len(cells))
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"]: set(m.get("workloads", cells)) for m in
           bench["end_to_end"]}
    assert e2e["setup_s"] == set(cells)
    for m in bench["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(m.get("workloads", cells)) <= e2e[m["moves"]]
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for name in cells:
        assert sum(name in s for s in e2e.values()) >= 2
        assert any(name in m.get("workloads", cells)
                   for m in bench["per_layer"])


def test_new_files_add_a_cell_and_a_metric(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    before = snapshot(tmp_path / "bench")

    with open(tmp_path / "BENCHMARK.json") as f:
        bench = json.load(f)
    with open(tmp_path / "bench" / "traffic" / "decode-backlog.json") as f:
        mix = json.load(f)
    mix.update(loop="open", rate_per_s=1.0)
    (tmp_path / "bench" / "traffic" / "chat-trickle.json").write_text(
        json.dumps(mix))
    cell = "granite-3-8b.l16.chat-trickle"
    (tmp_path / "bench" / "limits" / f"{cell}.json").write_text(json.dumps(
        {"served_logit_gap": {"limit": 1.0}, "tokens_compared": {"limit": 1}}))
    (tmp_path / "bench" / "metrics" / "tick_count.py").write_text(
        "def read(run):\n    return float(len(run['ticks'])) or None\n")
    bench["workloads"].append({"name": cell, "config": "granite-3-8b.l16",
                               "traffic": "chat-trickle", "chips": 1,
                               "why": "a trickle"})
    bench["per_layer"].append({"name": "tick_count", "unit": "ticks",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "Scheduler", "moves": "tokens_per_s",
                               "workloads": [cell]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    loaded = spec.load_cell(cell, root=str(tmp_path))
    assert loaded.traffic["rate_per_s"] == 1.0
    assert "tick_count" in [m["name"] for m in loaded.per_layer]
    read = spec.metric_reader("tick_count", root=str(tmp_path))
    assert read({"ticks": [1, 2, 3]}) == 3.0
    assert read({"ticks": []}) is None
    old = spec.load_cell("granite-3-8b.l16.decode-backlog",
                         root=str(tmp_path))
    assert "tick_count" not in [m["name"] for m in old.per_layer]
    after = snapshot(tmp_path / "bench")
    assert all(after[k] == v for k, v in before.items())


def test_unknown_names_are_errors(tmp_path):
    with pytest.raises(spec.SpecError):
        spec.load_cell("no-such-cell")
    with pytest.raises(spec.SpecError):
        spec.metric_reader("no_such_metric")
    with pytest.raises(spec.SpecError):
        spec.peaks_for("TPU v99")
    assert spec.peaks_for("TPU v5 lite")["bf16_flop_per_s"] == 197e12
