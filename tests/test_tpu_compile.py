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
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.kernels import ops

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
