"""Ahead-of-time compiles of the serving kernels for a described TPU v5e.

The TPU compiler is installed with JAX, so a ``v5e:2x2`` topology can be
described and compiled for without a chip attached.  These tests catch
what interpret mode cannot: block shapes the Mosaic compiler refuses,
primitives it cannot lower, and kernels that fall out of the program.
Shapes are granite-3-8b's published widths (32 query heads, 8 KV heads of
128, vocab 49155 padded to 49408, 8 BPD heads).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports every test file.
"""
import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.config.registry import get_config
from repro.kernels import ops
from repro.models import moe

B, KQ, H, KV, HD, L, PAGE = 8, 8, 32, 8, 128, 2048, 16
VERIFY_ROWS, VOCAB = 16, 49408


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    # the kernel is in the program as a Mosaic custom call, not interpreted
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("criterion,kw", [
    ("exact", {}),
    ("topk", {"top_k": 2}),
    ("distance", {"epsilon": 2.0}),
])
def test_fused_verify_compiles(spec, criterion, kw):
    fn = functools.partial(ops.fused_verify, criterion=criterion,
                           interpret=False, **kw)
    _compile(fn, spec((VERIFY_ROWS, KQ, VOCAB), jnp.float32),
             spec((VERIFY_ROWS, KQ), jnp.int32))


def test_fused_verify_compiles_on_model_sharded_mesh(topo):
    """Inside a (1, 4) mesh with the vocab sharded over ``model`` the
    kernel runs per batch shard in shard_map, the vocab gathered
    explicitly — GSPMD cannot partition a Mosaic call."""
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    logits = jax.ShapeDtypeStruct(
        (VERIFY_ROWS, KQ, VOCAB), jnp.float32,
        sharding=NamedSharding(mesh, PartitionSpec(None, None, "model")))
    props = jax.ShapeDtypeStruct((VERIFY_ROWS, KQ), jnp.int32,
                                 sharding=NamedSharding(mesh, PartitionSpec()))
    fn = functools.partial(ops.fused_verify, criterion="exact",
                           interpret=False)
    with jax.set_mesh(mesh):
        hlo = _compile(fn, logits, props).as_text()
    assert "all-gather" in hlo


def test_verify_attention_compiles(spec):
    q, kv = spec((B, KQ, H, HD), jnp.bfloat16), spec((B, L, KV, HD),
                                                     jnp.bfloat16)
    fn = functools.partial(ops.verify_attention, interpret=False)
    _compile(fn, q, kv, kv, spec((B, KQ), jnp.int32), spec((B, L), jnp.int32))


def test_tree_verify_attention_compiles(spec):
    q, kv = spec((B, KQ, H, HD), jnp.bfloat16), spec((B, L, KV, HD),
                                                     jnp.bfloat16)
    pos = spec((B, L), jnp.int32)
    fn = functools.partial(ops.tree_verify_attention, interpret=False)
    _compile(fn, q, kv, kv, spec((B, KQ), jnp.int32), pos, pos,
             spec((B, KQ), jnp.int32))


def test_paged_verify_attention_compiles(spec):
    pages = L // PAGE
    pool = spec((1 + B * pages, PAGE, KV, HD), jnp.bfloat16)
    fn = functools.partial(ops.paged_verify_attention, interpret=False)
    _compile(fn, spec((B, KQ, H, HD), jnp.bfloat16), pool, pool,
             spec((B, pages), jnp.int32), spec((B, KQ), jnp.int32),
             spec((B, L), jnp.int32))


# Memory-space moves the TPU compiler adds on its own (weights prefetched
# in slices, reassembled by ConcatBitcast): no op of the model's, no scope.
_COMPILER_MOVES = ("copy-start", "copy-done", "slice-start", "slice-done")


def _unscoped_large_ops(hlo: str, prefix: str, min_bytes: int):
    """Instructions of the entry computation that write a floating-point
    array of at least ``min_bytes`` and carry no ``op_name`` with a
    component starting with ``prefix``."""
    entry = hlo[hlo.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    itemsize = {"bf16": 2, "f16": 2, "f32": 4}
    out = []
    for line in entry.splitlines()[1:]:
        m = re.match(r"\s*(?:ROOT )?%\S+ = (bf16|f16|f32)\[([\d,]*)\]\S* "
                     r"([\w-]+)\(", line)
        if not m or m.group(3) in _COMPILER_MOVES + ("parameter", "bitcast") or (
                'custom_call_target="ConcatBitcast"' in line):
            continue
        size = itemsize[m.group(1)] * math.prod(
            int(n) for n in m.group(2).split(",") if n)
        name = re.search(r'op_name="([^"]*)"', line)
        if size >= min_bytes and not (name and any(
                part.startswith(prefix) for part in name.group(1).split("/"))):
            out.append(line.strip()[:160])
    return out


def test_moe_layer_ops_keep_their_scopes(spec):
    """Every op of a DeepSeek-V2-Lite MoE layer that writes more than its
    input (≥ 4 MiB), at the published widths and the benchmark's
    verify-step shape (32 rows of 8), carries a ``moe.*`` name after the
    TPU compile, so a trace charges the dispatch to ``moe.dispatch``.  A
    scatter-add into a zeroed buffer lost its ``op_name`` here (the zero
    fill and the scatter fusion, 67 MB each a layer)."""
    cfg = get_config("deepseek-v2-lite")
    params = jax.tree.map(
        lambda a: spec(a.shape, a.dtype),
        jax.eval_shape(lambda k: moe.moe_init(k, cfg, dtype=jnp.bfloat16),
                       jax.random.PRNGKey(0)))

    def layer(p, x):
        return moe.moe_apply(p, cfg, x, full_capacity=True)[0]

    hlo = jax.jit(layer).lower(
        params, spec((32, 8, cfg.d_model), jnp.bfloat16)).compile().as_text()
    assert "moe.dispatch" in hlo and "moe.experts" in hlo
    assert _unscoped_large_ops(hlo, "moe.", 4 << 20) == []


@pytest.mark.parametrize("shape", [(32, 8), (1, 512)],
                         ids=["verify", "admission"])
def test_moe_no_drop_layer_computes_only_routed_rows(spec, shape):
    """A DeepSeek-V2-Lite MoE layer on the no-drop path, compiled for the
    v5e at the benchmark's verify-step shape (32 rows of 8) and a
    512-token admission: its FLOPs stay within 1.5× of the work it needs
    (B·S·6 routed expert rows, the router and the shared experts).  A
    full-capacity buffer (B·Ep·S rows) or a masked dense product over all
    64 experts counts 8× or more."""
    cfg = get_config("deepseek-v2-lite")
    params = jax.tree.map(
        lambda a: spec(a.shape, a.dtype),
        jax.eval_shape(lambda k: moe.moe_init(k, cfg, dtype=jnp.bfloat16),
                       jax.random.PRNGKey(0)))
    tokens = math.prod(shape)
    d, ff, sff = cfg.d_model, cfg.d_ff, cfg.shared_expert_d_ff
    needed = 2 * tokens * d * (cfg.num_experts_per_tok * 3 * ff
                               + cfg.num_experts + 3 * sff)

    def layer(p, x):
        return moe.moe_apply(p, cfg, x, full_capacity=True)[0]

    compiled = jax.jit(layer).lower(
        params, spec(shape + (d,), jnp.bfloat16)).compile()
    cost = compiled.cost_analysis()
    flops = (cost[0] if isinstance(cost, list) else cost)["flops"]
    assert needed <= flops <= 1.5 * needed, (flops, needed)
