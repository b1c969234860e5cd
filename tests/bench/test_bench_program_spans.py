"""A traced smoke-size rehearsal on the CPU reads the program's own host-gap
counter, and the readers of the program's scopes and spans stay silent
where the trace holds no device plane."""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness, spec  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
NEW = ("heads_ms_per_step", "trunk_ms_per_step", "sync_latency_ms",
       "host_gap_ms_per_step")


def test_traced_rehearsal_reads_the_host_gap():
    with open(os.path.join(HERE, "smoke_config.json")) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "smoke_traffic.json")) as f:
        mix = json.load(f)
    mix.update(loop="closed", name="smoke")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert {m["name"] for m in bench["per_layer"]} >= set(NEW)
    cell = spec.Cell(name="smoke", chips=1, config=config,
                     model=spec.load_model(config), traffic=mix,
                     limits={"served_logit_gap": {"limit": 0.01},
                             "tokens_compared": {"limit": 32}},
                     end_to_end=bench["end_to_end"],
                     per_layer=bench["per_layer"])
    # the trace goes where a chip run puts it, which the readers search
    res = harness.run_cell(cell, 2**31 + 991, 2.5, True,
                           t_proc0=time.monotonic(), require_tpu=False,
                           peaks={"bf16_flop_per_s": 1e12,
                                  "hbm_bytes_per_s": 1e11},
                           cache=False, warm_timeout=60.0)
    assert res["correct"], res["checks"]
    gap = res["metrics"]["host_gap_ms_per_step"]
    assert gap["unit"] == "ms" and 0 < gap["value"] < 1e3
    # the CPU trace has no device plane: no step execution to read
    for name in ("heads_ms_per_step", "trunk_ms_per_step",
                 "sync_latency_ms"):
        assert name not in res["metrics"]
