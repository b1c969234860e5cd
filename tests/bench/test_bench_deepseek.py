"""DeepSeek-V2 in the benchmark: its family (``bench/models/deepseek_v2.py``)
and its cell, the open-loop chat-steady cell, and the readers of the
model's own scopes (``bench/model_scopes.py``), on the CPU.

The smoke configuration (``deepseek_smoke_config.json``) keeps the
published equations at toy widths: latent attention with YaRN, a dense
layer 0, then 8 experts of which 6 are chosen, unrenormalised, and 2
ungated shared experts."""
import functools
import json
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, HERE]

from bench import flops, harness, model_scopes, spec  # noqa: E402
from bench import span_reduce  # noqa: E402

CELL = "deepseek-v2-lite.l9.decode-backlog"
SEED = 2**31 + 5113
PEAKS = {"bf16_flop_per_s": 1e12, "hbm_bytes_per_s": 1e11}
# the program serves in bf16 against the float32 reference: sound runs read
# a widest gap of 0.035, the float8 control 0.56-0.87 (CPU, seeds 1-3 and
# SEED); the limit lies between, with room on both sides
LIMIT = 0.15


def _json(path):
    with open(path) as f:
        return json.load(f)


SMOKE = _json(os.path.join(HERE, "deepseek_smoke_config.json"))


def smoke_cell(traffic_path, config=SMOKE):
    mix = _json(traffic_path)
    mix.update(name="smoke")
    bench = _json(os.path.join(ROOT, "BENCHMARK.json"))
    return spec.Cell(name="smoke", chips=1, config=dict(config),
                     model=spec.load_model(config), traffic=mix,
                     limits={"served_logit_gap": {"limit": LIMIT},
                             "tokens_compared": {"limit": 32}},
                     end_to_end=bench["end_to_end"],
                     per_layer=bench["per_layer"])


def run(cell, control=False):
    return harness.run_cell(
        cell, SEED, 2.5, False, t_proc0=time.monotonic(), require_tpu=False,
        peaks=PEAKS, cache=False, warm_timeout=60.0, control=control)


def test_the_family_loads_through_spec():
    cell = spec.load_cell(CELL)
    assert cell.model.__name__.endswith("deepseek_v2")
    cfg = cell.model.program_config(cell.config)
    assert (cfg.num_layers, cfg.kv_lora_rank, cfg.num_experts,
            cfg.num_experts_per_tok, cfg.num_shared_experts) == (9, 512, 64, 6,
                                                                 2)
    assert (cfg.d_ff, cfg.dense_d_ff, cfg.shared_expert_d_ff) == (1408, 10944,
                                                                  2816)
    assert not cfg.norm_topk_prob and not cfg.shared_expert_gated
    assert cfg.padded_vocab_size == 102400 and not cfg.tie_embeddings
    names = {m["name"] for m in cell.per_layer}
    assert {"moe_ms_per_step", "mla_ms_per_step", "moe_roofline"} <= names


@pytest.mark.parametrize("config", ["cell", "smoke"])
def test_layout_and_program_config_agree_with_the_program(config):
    c = spec.load_cell(CELL).config if config == "cell" else SMOKE
    model = spec.load_model(c)
    harness.check_layout(model, c, model.program_config(c))


def test_a_config_the_program_cannot_run_is_refused():
    model = spec.load_model(SMOKE)
    for key, bad in (("q_lora_rank", 1536), ("scoring_func", "sigmoid"),
                     ("topk_method", "group_limited_greedy")):
        with pytest.raises(spec.SpecError, match=key):
            model.program_config(dict(SMOKE, **{key: bad}))


def test_rehearsal_of_the_deepseek_smoke_cell_and_its_control():
    """A whole run through the HTTP server, engine and check: correct; the
    float8 control in the program's place reads a far wider gap."""
    cell = smoke_cell(os.path.join(HERE, "smoke_traffic.json"))
    res = run(cell)
    assert res["correct"], res["checks"]
    assert res["checks"]["tokens_compared"]["value"] >= 32
    assert res["metrics"]["tokens_per_s"]["value"] > 0
    gap = res["checks"]["served_logit_gap"]["value"]
    ctrl = run(cell, control=True)
    assert not ctrl["correct"], ctrl["checks"]
    assert ctrl["checks"]["served_logit_gap"]["value"] > 3 * max(gap, 0.01)


def test_rehearsal_of_an_open_loop_chat_steady_run():
    """The chat-steady mix as it stands, at toy lengths and rate: open
    loop, granite's smoke model, correct."""
    mix = _json(os.path.join(ROOT, "bench", "traffic", "chat-steady.json"))
    assert mix["loop"] == "open" and mix["rate_per_s"] > 0
    mix.update(rate_per_s=20.0, preroll_s=1.0, stall_s=1.5, check_tokens=64,
               check_requests=4,
               prompt_len={"median": 8, "sigma": 0.6, "min": 4, "max": 16},
               output_len={"median": 16, "sigma": 0.6, "min": 8, "max": 32},
               engine={"num_slots": 4, "max_prompt_len": 16,
                       "max_new_cap": 32})
    path = os.path.join(os.environ.get("TMPDIR", "/tmp"),
                        f"chat_steady_smoke_{os.getpid()}.json")
    with open(path, "w") as f:
        json.dump(mix, f)
    granite = _json(os.path.join(HERE, "smoke_config.json"))
    cell = smoke_cell(path, config=granite)
    cell = cell._replace(limits={"served_logit_gap": {"limit": 0.01},
                                 "tokens_compared": {"limit": 32}})
    res = run(cell)
    os.remove(path)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


def test_the_sweep_reports_each_rate(tmp_path, monkeypatch, capsys):
    """``bench/sweep.py`` over an open-loop cell: one line per rate with
    the queue at the window's start and end and whether the rate held."""
    granite = _json(os.path.join(HERE, "smoke_config.json"))
    mix = _json(os.path.join(HERE, "smoke_traffic.json"))
    mix.update(loop="open")
    path = tmp_path / "open.json"
    path.write_text(json.dumps(mix))
    cell = smoke_cell(str(path), config=granite)._replace(
        limits={"served_logit_gap": {"limit": 0.01},
                "tokens_compared": {"limit": 32}})
    monkeypatch.setattr(spec, "load_cell", lambda name: cell)
    monkeypatch.setattr(harness, "run_cell", functools.partial(
        harness.run_cell, require_tpu=False, peaks=PEAKS, cache=False,
        warm_timeout=60.0))
    monkeypatch.setattr(harness, "serve", harness.serve)
    sweep = spec._load_module(os.path.join(ROOT, "bench", "sweep.py"),
                              "tool", "sweep")
    assert sweep.main(["--workload", "smoke", "--rates", "10",
                       "--seconds", "2.5", "--out",
                       str(tmp_path / "sweep.jsonl")]) == 0
    line = json.loads((tmp_path / "sweep.jsonl").read_text())
    assert line["rate_per_s"] == 10.0 and line["correct"]
    assert line["sustained"] == (line["queue_end"] <= line["queue_start"])
    assert line["metrics"]["tokens_per_s"] > 0 and line["ttft_p90_s"] > 0


def test_the_work_counts_the_needed_expert_rows():
    """A full batch touches every expert: the routed work is B·k·top-k rows
    and all 64 experts' weights of the 8 MoE layers, read once."""
    c = spec.load_cell(CELL).config
    model = spec.load_model(c)
    ctxs = [300] * 32
    moe = model.moe_step(c, ctxs)
    assert moe["flops"] == 2 * 8 * 32 * 8 * 6 * 3 * 2048 * 1408
    assert moe["bytes"] == pytest.approx(2 * 8 * 64 * 3 * 2048 * 1408,
                                         rel=1e-9)
    step = flops.verify_step({"model": model, "config": c}, ctxs)
    assert step["bytes"] > moe["bytes"] and step["flops"] > moe["flops"]
    # one row touches fewer experts than 64
    assert model.moe_step(c, [300])["bytes"] < moe["bytes"] / 1.5


# ---------------------------------------------------------------------------
# The readers of the model's scopes, on a synthetic trace
# ---------------------------------------------------------------------------

MS = 1_000_000_000  # ps


def xspace(scoped=True):
    """One device plane: two step executions (0-10, 20-30 ms) and an
    admission (40-50 ms); ops under nested model scopes when ``scoped``."""
    X = span_reduce._xspace_class()
    space = X()
    dev = space.planes.add(name="/device:TPU:0")
    dev.stat_metadata.add(key=1).value.name = "tf_op"
    names = ["jit_step_windowed(1)", "jit_admit(2)"]
    ops = [
        ("jit(step_windowed)/bpd.trunk/mla.project/dot_general", 0, 1),
        ("jit(step_windowed)/bpd.trunk/mla.attend/dot_general", 1, 2),
        ("jit(step_windowed)/bpd.trunk/moe.route/top_k", 2, 3),
        ("jit(step_windowed)/bpd.trunk/moe.experts/dot_general", 3, 8),
        ("jit(step_windowed)/bpd.heads/dot_general", 8, 10),
        ("jit(step_windowed)/bpd.trunk/moe.experts/dot_general", 20, 26),
        ("jit(step_windowed)/bpd.trunk/mla.out/dot_general", 26, 27),
        ("jit(step_windowed)/bpd.heads/dot_general", 27, 30),
        ("jit(admit)/bpd.prefill/moe.experts/dot_general", 40, 50),
    ]
    for i, n in enumerate(names):
        dev.event_metadata.add(key=i + 1).value.name = n
    for j, (op, _, _) in enumerate(ops):
        md = dev.event_metadata.add(key=100 + j).value
        md.name = f"fusion.{j}"
        if scoped:
            md.stats.add(metadata_id=1, str_value=op)
        else:
            md.stats.add(metadata_id=1, str_value=op.split("/m")[0])
    mods = dev.lines.add(name="XLA Modules", timestamp_ns=0)
    for key, a, b in ((1, 0, 10), (1, 20, 30), (2, 40, 50)):
        mods.events.add(metadata_id=key, offset_ps=a * MS,
                        duration_ps=(b - a) * MS)
    line = dev.lines.add(name="XLA Ops", timestamp_ns=0)
    for j, (_, a, b) in enumerate(ops):
        line.events.add(metadata_id=100 + j, offset_ps=a * MS,
                        duration_ps=(b - a) * MS)
    host = space.planes.add(name="/host:CPU")
    host.event_metadata.add(key=1).value.name = "bench.window"
    host.lines.add(name="main", timestamp_ns=0).events.add(
        metadata_id=1, offset_ps=0, duration_ps=60 * MS)
    return space.SerializeToString()


@pytest.fixture
def traced(tmp_path, monkeypatch):
    def write(scoped=True):
        t0 = time.monotonic() - 1.0
        d = tmp_path / "trace" / "plugins" / "profile" / "1"
        d.mkdir(parents=True, exist_ok=True)
        (d / "host.xplane.pb").write_bytes(xspace(scoped))
        monkeypatch.setattr(model_scopes, "run_scopes", functools.partial(
            model_scopes.run_scopes.__wrapped__
            if hasattr(model_scopes.run_scopes, "__wrapped__")
            else model_scopes.run_scopes, root=str(tmp_path / "trace")))
        c = spec.load_cell(CELL).config
        return {"t0": t0, "t1": t0 + 0.060, "config": c,
                "model": spec.load_model(c), "chips": 1,
                "peaks": spec.peaks_for("TPU v5 lite"),
                "steps": [[300] * 32, [310] * 32]}
    return write


def test_model_scopes_reduce_nested_scopes(traced):
    run_ = traced()
    red = model_scopes.run_scopes(run_)
    assert red["steps"] == 2
    assert red["scope_s"]["moe.experts"] == pytest.approx(0.011)
    assert red["scope_s"]["mla.attend"] == pytest.approx(0.001)
    # the admission's experts are no step's
    assert sum(red["scope_s"].values()) == pytest.approx(0.020)
    assert model_scopes.model_scope("a/bpd.trunk/moe.shared/dot:") == \
        "moe.shared"


def test_the_new_readers_on_a_synthetic_trace(traced):
    run_ = traced()
    read = {n: spec.metric_reader(n) for n in
            ("moe_ms_per_step", "mla_ms_per_step", "moe_roofline")}
    assert read["moe_ms_per_step"](run_) == pytest.approx(6.0)   # (6+6)/2
    assert read["mla_ms_per_step"](run_) == pytest.approx(1.5)   # (1+1+1)/2
    least = sum(flops.least_seconds(run_["model"].moe_step(
        run_["config"], ctx), run_["peaks"]) for ctx in run_["steps"]) / 2
    assert read["moe_roofline"](run_) == pytest.approx(
        100 * least / 0.0055)


def test_the_new_readers_are_silent_without_the_scopes(traced):
    """A program without the model's scopes (the parent of this change, or
    granite) gives no reading, and no error."""
    run_ = traced(scoped=False)
    for name in ("moe_ms_per_step", "mla_ms_per_step", "moe_roofline"):
        assert spec.metric_reader(name)(run_) is None
