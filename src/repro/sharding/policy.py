"""Sharding policy: parameter/cache/batch PartitionSpecs for the production
mesh.

Scheme (Megatron-style tensor parallelism under GSPMD):
  * ``model`` axis: attention heads / kv heads, FFN width, experts, vocab,
    SSM inner channels, BPD head hidden width.
  * ``data`` (+ ``pod``) axes: the batch dimension of activations, caches
    and inputs.  Gradient all-reduce over data/pod is inserted by GSPMD.
  * Norm scales, routers, token-shift anchors, small LoRA factors: replicated.

Everything is rule-based on parameter path names so new modules inherit
sensible defaults; rules are ordered, first match wins.
"""
from __future__ import annotations

import math
import re
from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config import ModelConfig
from repro.utils.tree import tree_map_with_name

Pytree = Any

# (regex on 'a/b/c' param path, PartitionSpec) — first match wins.
PARAM_RULES: Tuple[Tuple[str, P], ...] = (
    # --- embeddings / unembedding -------------------------------------------
    (r"(^|/)embed/table$", P("model", None)),
    (r"(^|/)src_embed/table$", P("model", None)),
    (r"(^|/)lm_head/w$", P(None, "model")),
    (r"(^|/)pos_embed$", P()),
    (r"(^|/)enc_pos$", P()),
    (r"(^|/)meta_tokens$", P()),
    (r"(^|/)mask_embed$", P()),
    # --- attention ------------------------------------------------------------
    (r"(attn|cross)/wq$", P(None, "model", None)),
    (r"(attn|cross)/wk$", P(None, "model", None)),
    (r"(attn|cross)/wv$", P(None, "model", None)),
    (r"(attn|cross)/wo$", P("model", None, None)),
    # MLA: the latent projection serves every head; its up-projections
    # (the halves of kv_b_proj) split by head like wq
    (r"attn/wkv_a$", P()),
    (r"attn/wkb$", P(None, "model", None)),
    (r"attn/wvb$", P(None, "model", None)),
    # --- MoE -------------------------------------------------------------------
    (r"moe/router/", P()),
    (r"moe/w1$", P("model", None, None)),
    (r"moe/w2$", P("model", None, None)),
    (r"moe/w3$", P("model", None, None)),
    (r"moe/shared/w1/w$", P(None, "model")),
    (r"moe/shared/w3/w$", P(None, "model")),
    (r"moe/shared/w2/w$", P("model", None)),
    (r"moe/shared/gate/", P()),
    # --- dense MLP --------------------------------------------------------------
    (r"mlp/w1/w$", P(None, "model")),
    (r"mlp/w3/w$", P(None, "model")),
    (r"mlp/w2/w$", P("model", None)),
    # --- RWKV6 -------------------------------------------------------------------
    (r"tm/w[rkvg]$", P(None, "model")),
    (r"tm/wo$", P("model", None)),
    (r"tm/u$", P("model", None)),
    (r"tm/(mu|mu_x|mix_A|mix_B|w0|decay_A|decay_B)$", P()),
    (r"tm/ln_x/", P()),
    (r"cm/wk$", P(None, "model")),
    (r"cm/wv$", P("model", None)),
    (r"cm/wr$", P(None, "model")),
    (r"cm/mu_[kr]$", P()),
    # --- Mamba (hymba SSM heads) ---------------------------------------------------
    (r"mamba/in_proj/w$", P(None, "model")),
    (r"mamba/conv_w$", P(None, "model")),
    (r"mamba/conv_b$", P("model")),
    (r"mamba/x_proj/w$", P("model", None)),
    (r"mamba/dt_proj/w$", P(None, "model")),
    (r"mamba/dt_proj/b$", P("model")),
    (r"mamba/A_log$", P("model", None)),
    (r"mamba/D$", P("model")),
    (r"mamba/out_proj/w$", P("model", None)),
    # --- BPD heads (the paper's multi-output layer) ---------------------------------
    (r"bpd_heads/w1$", P(None, None, "model")),
    (r"bpd_heads/b1$", P(None, "model")),
    (r"bpd_heads/w2$", P(None, "model", None)),
    (r"bpd_heads/b2$", P()),
)

DEFAULT_SPEC = P()  # norms, biases, scalars


def _spec_for(name: str) -> P:
    for pattern, spec in PARAM_RULES:
        if re.search(pattern, name):
            return spec
    return DEFAULT_SPEC


def _divisible(spec: P, shape, mesh: Mesh) -> P:
    """Drop sharding on dims the array does not divide evenly.  pjit argument
    shardings require exact divisibility (GSPMD only pads intermediates), so
    e.g. kv_heads=5 or vocab not a multiple of the model axis falls back to
    replicated on that dim.  Vocab dims avoid this via padded_vocab_size."""
    out = []
    for dim, ax in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if ax is None:
            out.append(None)
            continue
        size = math.prod(mesh.shape[a] for a in (ax if isinstance(ax, tuple) else (ax,)))
        out.append(ax if dim % size == 0 else None)
    return P(*out)


def param_specs(params: Pytree, mesh: Mesh) -> Pytree:
    """PartitionSpec pytree mirroring ``params``."""
    return tree_map_with_name(
        lambda name, x: _divisible(_spec_for(name), x.shape, mesh), params)


def param_shardings(params: Pytree, mesh: Mesh) -> Pytree:
    return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s),
                                  param_specs(params, mesh))


def bundle_param_shardings(bundles: dict, mesh: Mesh) -> dict:
    """Per-bundle parameter shardings: {name: NamedSharding pytree} for a
    ``{name: core.bundle.ModelBundle}`` mapping — what a ``DecodeSession``
    device_puts each auxiliary bundle with.  Every bundle's params go
    through the same path-rule table, so a draft model's attention/MLP
    weights shard on the ``model`` axis exactly like the primary model's
    (dims that don't divide fall back to replicated per leaf)."""
    return {name: param_shardings(b.params, mesh)
            for name, b in bundles.items()}


# ---------------------------------------------------------------------------
# Batch / activation / cache specs
# ---------------------------------------------------------------------------


def batch_axes(mesh: Mesh, batch_size: int):
    """Mesh axes to shard the batch dim over (None = replicate)."""
    names = mesh.axis_names
    cand = tuple(a for a in ("pod", "data") if a in names)
    if cand:
        n = math.prod(mesh.shape[a] for a in cand)
        if n and batch_size % n == 0:
            return cand
    if "data" in names and batch_size % mesh.shape["data"] == 0:
        return ("data",)
    return None


def data_spec(mesh: Mesh, batch_size: int, ndim: int, *,
              extra: Optional[dict] = None) -> P:
    """P(batch_axes, None, ...) for a batch-leading array."""
    ax = batch_axes(mesh, batch_size)
    dims = [ax] + [None] * (ndim - 1)
    if extra:
        for i, a in extra.items():
            dims[i] = a
    return P(*dims)


def batch_specs(mesh: Mesh, batch: Pytree) -> Pytree:
    """Shard every batch leaf on its leading dim."""
    return jax.tree_util.tree_map(
        lambda x: data_spec(mesh, x.shape[0], x.ndim), batch)


def cache_specs(cfg: ModelConfig, caches: Pytree, mesh: Mesh,
                batch_size: int) -> Pytree:
    """Decode caches: batch over data axes; kv-heads over model where the
    head count divides the axis, otherwise the buffer LENGTH dim shards over
    model (flash-decoding-style sequence sharding: the softmax/PV reductions
    over the sharded length become GSPMD all-reduces, and attn_buf_len pads
    the buffer to a multiple of 256 so it always divides)."""
    ax = batch_axes(mesh, batch_size)
    msz = mesh.shape.get("model", 1)
    kv_divides = cfg.num_kv_heads and cfg.num_kv_heads % msz == 0

    def spec(name: str, x) -> P:
        if "/attn/" in name and name.endswith(("/kp", "/vp")):
            # paged page pool: pages are shared across rows (CoW prefix
            # reuse), so the pool NEVER shards over the data axes — it
            # replicates there, trading the dense layout's data-parallel
            # split for the much larger paging win.  kv-heads shard over
            # model when they divide; the page-gather read path cannot
            # length-shard, so the fallback is replication.
            if kv_divides:
                return _divisible(P(None, None, "model", None), x.shape, mesh)
            return P()
        if "/attn/" in name and name.endswith(("/ckv", "/kpe")):
            # MLA latent: one per token, shared by every head
            return P(ax, None, None)
        if name.endswith("/tbl"):
            return P(ax, None)
        if name.endswith("/pos"):
            if not kv_divides and x.ndim == 2 and x.shape[1] % msz == 0:
                return P(ax, "model")
            return P(ax, None)
        if "/attn/" in name and name[-2:] in ("/k", "/v"):
            if kv_divides:
                return _divisible(P(ax, None, "model", None), x.shape, mesh)
            return _divisible(P(ax, "model", None, None), x.shape, mesh)
        if "/tm/" in name:  # rwkv: state (B,H,D,D), shifts (B,d)
            if "state" in name:
                return _divisible(P(ax, "model", None, None), x.shape, mesh)
            return P(ax, None)
        if "/mamba/" in name:
            if name.endswith("/h") or "h_steps" in name:
                return _divisible(P(ax, "model", None), x.shape, mesh)
            return _divisible(P(ax, None, "model"), x.shape, mesh)
        # default: batch-leading
        return P(*([ax] + [None] * (x.ndim - 1)))

    return tree_map_with_name(spec, caches)


# ---------------------------------------------------------------------------
# Loop-carried decode state specs (BPDState / GreedyState / SlotBatch)
# ---------------------------------------------------------------------------


def state_specs(cfg: ModelConfig, state: Any, mesh: Mesh, *,
                batch_size: Optional[int] = None,
                draft_cfg: Optional[ModelConfig] = None,
                policy: Any = None) -> Any:
    """PartitionSpec pytree for a batch-leading decode loop state.

    ``state`` is any NamedTuple whose arrays lead with the batch dimension
    (``BPDState``, ``GreedyState``, the serving ``SlotBatch``) and whose
    ``caches`` field is a per-layer cache pytree: the caches get the full
    ``cache_specs`` treatment (kv-heads or buffer length over ``model``),
    every other (B, ...) array shards its leading dim over the data axes,
    and scalars (loop counters) are replicated.  Works on concrete arrays
    and on ``ShapeDtypeStruct`` trees alike.

    The ``policy_state`` field (a ``core.policy.PolicyState`` pytree of
    loop-carried drafter/schedule state) is covered by the same rule: the
    policy contract requires batch-leading ``(B, ...)`` leaves, so e.g. an
    ``InputCopyDrafter``'s source batch or an ``AdaptiveSchedule``'s
    per-row cap shard over the data axes with the rest of the decode
    state.

    ``draft_cfg`` (the bound drafter's own model config — a
    ``DecodeSession`` reads it off ``policy.drafter.cfg``) upgrades
    model-backed drafter state: a drafter state dict carrying a
    ``"caches"`` cache pytree (the ``draft_model`` policy's loop-carried
    draft KV cache) gets the full ``cache_specs`` treatment under the
    DRAFT model's config instead of the generic batch-leading rule.

    ``policy`` (a bound ``core.policy.DecodePolicy``) is the per-group
    form of the same information: when given and ``draft_cfg`` is not,
    the draft config is read off ``policy.drafter.cfg`` — so callers that
    build specs for several policy slot groups (the serving engine) pass
    each group's own policy instead of one session-global draft config.
    """
    if draft_cfg is None and policy is not None:
        draft_cfg = getattr(policy.drafter, "cfg", None)
    b = batch_size if batch_size is not None else state.tokens.shape[0]
    ax = batch_axes(mesh, b)

    def leaf(x) -> P:
        if x.ndim >= 1 and x.shape[0] == b:
            return P(*([ax] + [None] * (x.ndim - 1)))
        return P()

    def policy_specs(ps):
        dstate = ps.drafter
        if (draft_cfg is not None and isinstance(dstate, dict)
                and "caches" in dstate):
            drafter = {k: cache_specs(draft_cfg, v, mesh, b)
                       if k == "caches"
                       else jax.tree_util.tree_map(leaf, v)
                       for k, v in dstate.items()}
        else:
            drafter = jax.tree_util.tree_map(leaf, dstate)
        return type(ps)(drafter=drafter,
                        schedule=jax.tree_util.tree_map(leaf, ps.schedule))

    fields = {}
    for name, val in state._asdict().items():
        if name == "caches" and val is not None:
            fields[name] = cache_specs(cfg, val, mesh, b)
        elif name == "policy_state" and hasattr(val, "drafter"):
            fields[name] = policy_specs(val)
        else:
            fields[name] = jax.tree_util.tree_map(leaf, val)
    return type(state)(**fields)


def slot_specs(cfg: ModelConfig, slots: Any, mesh: Mesh, *,
               draft_cfg: Optional[ModelConfig] = None,
               policy: Any = None) -> Any:
    """Specs for the serving engine's ``SlotBatch`` (slot dim == batch dim).

    Identical derivation to ``state_specs`` — the slot batch IS the decode
    batch; admission/eviction scatters stay local to the owning data shard.
    Called once per policy slot group: each group's ``SlotBatch`` is its
    own view of the engine's slot slab, so ``policy=`` (the GROUP's bound
    policy) lets a model-backed drafter's cache spec under its own draft
    config while other groups in the same engine spec generically.
    """
    return state_specs(cfg, slots, mesh, batch_size=slots.tokens.shape[0],
                       draft_cfg=draft_cfg, policy=policy)


def data_axis_size(mesh: Mesh) -> int:
    """Number of shards the batch/slot dim splits into on this mesh."""
    return math.prod(mesh.shape[a] for a in ("pod", "data")
                     if a in mesh.axis_names)


def active_mesh():
    """The mesh set by ``jax.set_mesh`` (readable while tracing), or None."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def maybe_shard(x, spec: P):
    """with_sharding_constraint that no-ops when no mesh is active, so model
    code can carry GSPMD hints without making tests mesh-dependent."""
    if active_mesh() is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def maybe_shard_expert(x):
    """Constraint for (B, Ep, C, d) expert-parallel MoE buffers: batch over
    the data axes, experts over model.  Axes are derived from the ACTIVE
    mesh (so the same model code lowers on single-pod and multi-pod meshes)
    and dropped when the dim doesn't divide (e.g. batch=1 long-context)."""
    mesh = active_mesh()
    if mesh is None:
        return x
    b_ax = batch_axes(mesh, x.shape[0])
    spec = _divisible(P(b_ax, "model", None, None), x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, spec)


def named(mesh: Mesh, tree_of_specs: Pytree) -> Pytree:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree_of_specs,
        is_leaf=lambda x: isinstance(x, P))
