"""A smoke-size rehearsal of a cell's whole run on the CPU, and the faults
that the check has to catch.

Every run goes through ``bench.harness.run_cell`` (weights from the seed,
the HTTP/SSE server, pre-roll, window, reference check), skipping only the
look for a chip.  Each fault breaks the timed path underneath, where the
program produces its work, and ``correct`` must come out false.
"""
import json
import os
import sys
import time

import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness, spec  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
PEAKS = {"bf16_flop_per_s": 1e12, "hbm_bytes_per_s": 1e11}
SEED = 2**31 + 977          # seeds may exceed 32 signed bits
# toy widths in bf16 against the float32 reference: sound runs read gaps
# up to 0.002 and the float8 control 0.025-0.06 (CPU, seeds 1-3)
LIMIT = 0.01


def smoke_cell(loop="closed"):
    with open(os.path.join(HERE, "smoke_config.json")) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "smoke_traffic.json")) as f:
        mix = json.load(f)
    mix.update(loop=loop, name="smoke")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return spec.Cell(name="smoke", chips=1, config=config,
                     model=spec.load_model(config), traffic=mix,
                     limits={"served_logit_gap": {"limit": LIMIT},
                             "tokens_compared": {"limit": 32}},
                     end_to_end=bench["end_to_end"],
                     per_layer=bench["per_layer"])


def run(cell, trace=False, tmp_path=None, warm_timeout=60.0):
    return harness.run_cell(
        cell, SEED, 2.5, trace, t_proc0=time.monotonic(), require_tpu=False,
        peaks=PEAKS, cache=False, warm_timeout=warm_timeout,
        trace_dir=str(tmp_path / "trace") if trace else None)


@pytest.mark.parametrize("loop", ["closed", "open"])
def test_rehearsal_is_correct(loop):
    res = run(smoke_cell(loop))
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] > 0 and res["failed"] == 0
    names = set(res["metrics"])
    assert {"tokens_per_s", "tpot_p90_ms", "setup_s"} <= names
    assert res["metrics"]["tokens_per_s"]["value"] > 0
    assert res["checks"]["tokens_compared"]["value"] >= 32
    assert res["extra"]["compiles_in_window"] == 0


def test_traced_rehearsal_reports_counter_metrics(tmp_path):
    res = run(smoke_cell("open"), trace=True, tmp_path=tmp_path)
    assert res["correct"], res["checks"]
    # the CPU trace has no device plane: device metrics stay silent
    assert "verify_step_ms" not in res["metrics"]
    assert {"slot_occupancy", "accepted_per_step", "mfu"} <= set(
        res["metrics"])
    assert 0 < res["metrics"]["slot_occupancy"]["value"] <= 100
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def _roll_tokens(monkeypatch):
    """A token altered where it is produced: every logit row shifted by
    one id, so the served token is the reference's best plus one."""
    from repro.models import model as M

    orig = M.project_vocab
    monkeypatch.setattr(M, "project_vocab",
                        lambda p, cfg, h: jnp.roll(orig(p, cfg, h), 1, -1))


def _frozen_step(monkeypatch):
    """A step that returns its state unchanged."""
    from repro.core import decode

    monkeypatch.setattr(decode, "bpd_iteration",
                        lambda params, cfg, dec, backend, state, **kw: state)


def _half_batch(monkeypatch):
    """Half of the slot batch left out of every step."""
    from repro.core import decode

    orig = decode.bpd_iteration

    def half(params, cfg, dec, backend, state, *, active=None, **kw):
        b = state.proposals.shape[0]
        keep = jnp.arange(b) < b // 2
        active = keep if active is None else active & keep
        return orig(params, cfg, dec, backend, state, active=active, **kw)

    monkeypatch.setattr(decode, "bpd_iteration", half)


@pytest.mark.parametrize("fault", [_roll_tokens, _frozen_step, _half_batch],
                         ids=["token_altered", "state_unchanged",
                              "half_batch"])
def test_fault_makes_run_incorrect(monkeypatch, fault):
    fault(monkeypatch)
    res = run(smoke_cell("closed"), warm_timeout=3.0)
    assert not res["correct"], res["checks"]


def mesh_cell():
    cell = smoke_cell("closed")
    cell.config["mesh"] = [1, 4]
    return cell._replace(chips=4)


def test_rehearsal_on_a_four_device_mesh():
    """A configuration that states a (1, 4) mesh: weights drawn straight
    into the program's shardings, the served path tensor-parallel."""
    res = run(mesh_cell())
    assert res["correct"], res["checks"]


def test_exchange_left_out_makes_run_incorrect(monkeypatch):
    """The exchange between chips left out: each device keeps its own
    partial sum of the FFN's down projection instead of all-reducing it."""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.models import blocks, layers
    from repro.sharding.policy import active_mesh

    def mlp_without_exchange(p, x, *, act):
        if active_mesh() is None:
            return layers.mlp_apply(p, x, act=act)
        h = (layers.activation("silu", layers.dense_apply(p["w1"], x))
             * layers.dense_apply(p["w3"], x))
        lead = (None,) * (h.ndim - 1)
        return jax.shard_map(
            lambda h, w: h @ w, mesh=active_mesh(),
            in_specs=(P(*lead, "model"), P("model", None)),
            out_specs=P(*lead, None), check_vma=False)(
                h, p["w2"]["w"].astype(h.dtype))

    monkeypatch.setattr(blocks, "mlp_apply", mlp_without_exchange)
    res = run(mesh_cell(), warm_timeout=3.0)
    assert not res["correct"], res["checks"]
