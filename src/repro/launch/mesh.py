"""Production mesh construction (TPU v5e pods).

``make_production_mesh`` is a function (not a module-level constant) so that
importing this module never touches jax device state — the dry-run must set
XLA_FLAGS before anything initializes the backend.

Every mesh is built with ``Auto`` axes: the model and serving code is
written for GSPMD propagation (``with_sharding_constraint`` hints under
``jax.set_mesh``), while ``jax.make_mesh`` defaults to ``Explicit`` axes,
under which those hints are rejected.
"""
from __future__ import annotations

import jax

from repro.config import MeshConfig


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2×16×16 = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(mc: MeshConfig):
    return _auto_mesh(mc.shape, mc.axes)


def make_host_mesh(data: int = 1, model: int = 1, *, pod: int = 1,
                   require: bool = False):
    """Small host mesh over whatever devices exist (tests / local runs):
    ("data", "model"), or the production ("pod", "data", "model") shape
    when ``pod > 1`` — the host-scale twin of ``make_production_mesh``'s
    multi-pod layout (the slot slab shards over pod×data; see
    sharding.policy).  Axes shrink to fit the
    available device count unless ``require=True`` — then an
    under-provisioned host raises instead of silently degrading a sharded
    run to fewer shards (use
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` to fake N CPU
    devices)."""
    n = len(jax.devices())
    p = min(pod, n)
    d = min(data, max(n // p, 1))
    m = min(model, max(n // (p * d), 1))
    if require and (p, d, m) != (pod, data, model):
        need = pod * data * model
        raise RuntimeError(
            f"host mesh {pod}x{data}x{model} needs {need} devices, have "
            f"{n} — set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{need} before jax initializes")
    if pod > 1:
        return _auto_mesh((p, d, m), ("pod", "data", "model"))
    return _auto_mesh((d, m), ("data", "model"))


# Hardware constants for roofline analysis (TPU v5e, per chip)
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BW = 819e9                # B/s
ICI_BW = 50e9                 # B/s per link
