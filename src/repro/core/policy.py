"""Pluggable decode policies: Drafter × Acceptor × BlockSchedule.

The paper's speedups hinge on *what gets proposed* and *how it is accepted*
(§3 exact match, §5.1 top-k, §5.2 distance, §5.3 minimum block size).  A
``DecodePolicy`` makes those axes first-class objects instead of enum
branches inside the decode loop:

  * ``Acceptor``   — maps (proposals, verify p_1 logits) to per-position
    accept decisions.  Built-ins: ``ExactAcceptor`` (§3), ``TopKAcceptor``
    (§5.1), ``DistanceAcceptor`` (§5.2).
  * ``BlockSchedule`` — turns the accept mask into a per-row block size k̂,
    optionally with loop-carried state.  ``StaticSchedule`` is §5.3's
    minimum block size; ``AdaptiveSchedule`` generalizes it into a dynamic
    controller that grows/shrinks a per-row cap from the running acceptance
    rate.
  * ``Drafter``    — produces the next block of k proposals from the verify
    forward's own outputs (plus optional loop-carried state).
    ``HeadsDrafter`` is the paper's prediction heads; ``InputCopyDrafter``
    drafts from the source sentence (Aggressive-Decoding-style, for the
    paper's MT setting); ``TopKTreeDrafter`` drafts top-k candidates per
    slot and picks the chain that the strongest head (p_1) also scores
    highly.

Index convention (0-based within a block; see core/verify.py):

  * ``proposals[:, i]`` proposes the token at absolute position
    ``text_len + i`` (the next unwritten position is ``text_len``).
  * Slot 0 of a fresh draft MUST be the model's own verified greedy token
    (p_1's argmax at the accepted slot): acceptance treats slot 0 as
    unconditional (k̂ ≥ 1), so a drafter that puts anything else there
    changes the decoded output.  Every built-in drafter preserves this, so
    exact-acceptance decoding stays token-identical to greedy regardless of
    the drafter — drafts change *iteration counts*, never *tokens*.

Loop-carried policy state is a ``PolicyState(drafter=…, schedule=…)`` pytree
threaded through ``BPDState`` / ``SlotBatch``.  Every state leaf must be a
batch-leading ``(B, …)`` array (or absent): ``sharding.policy.state_specs``
then shards it over the data axes like any other per-row decode state, and
the serving engine can reset single rows on admit/evict.

String names resolve through ``resolve_policy`` (see ``POLICY_BUILDERS``);
the legacy ``DecodeConfig.criterion`` strings "exact" / "topk" / "distance"
remain valid aliases for the corresponding heads-drafted policies.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import DecodeConfig

I32 = jnp.int32


class PolicyState(NamedTuple):
    """Loop-carried policy state (a field of ``BPDState`` / ``SlotBatch``).

    Both fields are pytrees whose leaves are batch-leading ``(B, …)``
    arrays; ``()`` means stateless.  Kept as a NamedTuple so the pytree
    structure is stable across jit boundaries and ``state_specs`` can walk
    it like any other decode-state field.
    """

    drafter: Any = ()
    schedule: Any = ()


class DraftInputs(NamedTuple):
    """Everything one verify forward exposes to a ``Drafter``.

    ``logits`` holds the heads' logits at the accepted slot of the
    iteration that just verified the current block: row 0 is p_1 there
    (whose argmax is the verified greedy token), row i head p_{i+1}.  The
    step verifies from p_1 first and then runs the other heads at that one
    position, so drafting stays free (no extra model calls), exactly like
    the paper's combined scoring/proposal formulation (§4), and the heads
    at the other block positions, which no drafter reads, are never
    computed.  At prefill the position is the last context token.

    ``prev_token`` / ``aux`` are the bundle-aware model-call seam: a
    drafter backed by its own model (``core.draft.DraftModelDrafter``)
    reads its parameters from ``aux`` (the session's auxiliary
    ``ModelBundle`` params, keyed by bundle name) and uses ``prev_token``
    — the committed token at position ``text_len - 1`` — to keep its own
    loop-carried cache in sync with the verified stream.  Drafters that
    only read the verify forward ignore both.
    """

    logits: jnp.ndarray       # (B, block_k, V) head logits at the slot
    khat: jnp.ndarray         # (B,) accepted block size this iteration
    slot: jnp.ndarray         # (B,) the slot: max(k̂ - 1, 0), a tree's node
    text_len: jnp.ndarray     # (B,) text length AFTER accepting this block
    old_proposals: jnp.ndarray  # (B, k) the block that was just verified
    prev_token: Any = ()      # (B,) committed token at text_len - 1
    aux: Any = ()             # {bundle name: params} for model-backed drafters


# ---------------------------------------------------------------------------
# Acceptors (paper §3, §5.1, §5.2)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Acceptor:
    """Per-position acceptance rule.  Subclasses implement ``position_ok``
    on the (B, k-1) candidate slice; slot 0 is always accepted (k̂ ≥ 1).

    ``fused=True`` routes ``accepts`` through the one-pass Pallas kernel
    (``kernels.fused_verify``): the vocab-dimension argmax/top-k, the
    criterion compare, and the prefix-accept scan run as a single op that
    streams the (B, k, V) logits once instead of four separate XLA ops.
    Token-identical to the jnp path (same ``jnp.argmax`` tie-breaking);
    opt-in via ``DecodeConfig.fused_verify``.  Subclasses advertise their
    compile-time kernel variant through ``fused_spec``; ``None`` means no
    fused form exists and the jnp path is always used.
    """

    fused: bool = False

    def accepts(self, proposals: jnp.ndarray,
                p1_logits: jnp.ndarray) -> jnp.ndarray:
        """proposals (B, k) int32, p1_logits (B, k, V) -> (B, k) bool."""
        b, k = proposals.shape
        spec = self.fused_spec() if self.fused else None
        if spec is not None:
            from repro.kernels import ops

            acc, _, _, _ = ops.fused_verify(p1_logits[:, :k, :], proposals,
                                            **spec)
            return acc
        ver_logits = p1_logits[:, : k - 1, :]      # slot i-1 verifies slot i
        cand = proposals[:, 1:]
        ok = self.position_ok(cand, ver_logits)
        return jnp.concatenate([jnp.ones((b, 1), bool), ok], axis=1)

    def fused_spec(self) -> Optional[Dict]:
        """kwargs for ``kernels.ops.fused_verify`` (None: no fused form)."""
        return None

    def position_ok(self, cand: jnp.ndarray,
                    ver_logits: jnp.ndarray) -> jnp.ndarray:
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class ExactAcceptor(Acceptor):
    """§3: accept while the proposal equals the model's greedy token —
    output is token-identical to greedy decoding."""

    def position_ok(self, cand, ver_logits):
        return cand == jnp.argmax(ver_logits, axis=-1)

    def fused_spec(self):
        return {"criterion": "exact"}


@dataclasses.dataclass(frozen=True)
class TopKAcceptor(Acceptor):
    """§5.1: accept any proposal inside the verifier's top-k set."""

    top_k: int = 1

    def position_ok(self, cand, ver_logits):
        _, top_ids = jax.lax.top_k(ver_logits, self.top_k)
        return jnp.any(top_ids == cand[..., None], axis=-1)

    def fused_spec(self):
        return {"criterion": "topk", "top_k": self.top_k}


@dataclasses.dataclass(frozen=True)
class DistanceAcceptor(Acceptor):
    """§5.2: ordinal vocabularies — accept proposals within ``epsilon`` of
    the greedy token id."""

    epsilon: float = 0.0

    def position_ok(self, cand, ver_logits):
        return jnp.abs(cand - jnp.argmax(ver_logits, axis=-1)) <= self.epsilon

    def fused_spec(self):
        return {"criterion": "distance", "epsilon": self.epsilon}


# ---------------------------------------------------------------------------
# Block schedules (paper §5.3, generalized)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BlockSchedule:
    """Turns per-position accepts into a per-row block size k̂ (stateful)."""

    def init_state(self, b: int) -> Any:
        return ()

    def block_size(self, accepts: jnp.ndarray, remaining: jnp.ndarray,
                   state: Any):
        """accepts (B, k) bool, remaining (B,) int32 ->
        (k̂ (B,) int32 in [1, min(k, remaining)], new state)."""
        raise NotImplementedError


def _prefix_len(accepts: jnp.ndarray) -> jnp.ndarray:
    """Longest accepted prefix per row: (B, k) bool -> (B,) int32."""
    return jnp.sum(jnp.cumprod(accepts.astype(I32), axis=1), axis=1)


@dataclasses.dataclass(frozen=True)
class StaticSchedule(BlockSchedule):
    """§5.3 minimum block size: k̂ = max(prefix, min_block), clamped to the
    remaining budget.  Stateless — min_block=1 is the paper's default."""

    min_block: int = 1

    def block_size(self, accepts, remaining, state):
        khat = _prefix_len(accepts)
        if self.min_block > 1:
            khat = jnp.maximum(khat, min(self.min_block, accepts.shape[1]))
        return jnp.maximum(jnp.minimum(khat, remaining), 1), state


@dataclasses.dataclass(frozen=True)
class AdaptiveSchedule(BlockSchedule):
    """Dynamic §5.3: a per-row cap on k̂ driven by the running acceptance
    rate.  An EMA of k̂/k grows the cap (toward the full block) while
    acceptance is high and shrinks it (toward ``min_block``) when proposals
    keep missing — bounding the tokens a row can over-commit on workloads
    where its acceptance rate has collapsed.

    State (per row): ``rate`` f32 EMA of k̂/cap, ``cap`` int32 current cap.
    """

    min_block: int = 1
    decay: float = 0.7          # EMA decay of the acceptance-rate estimate
    grow: float = 0.8           # rate above which the cap grows by 1
    shrink: float = 0.4         # rate below which the cap shrinks by 1

    def init_state(self, b: int) -> Any:
        return {"rate": jnp.ones((b,), jnp.float32),
                "cap": jnp.full((b,), jnp.iinfo(jnp.int32).max, I32)}

    def block_size(self, accepts, remaining, state):
        k = accepts.shape[1]
        floor = max(min(self.min_block, k), 1)
        cap = jnp.clip(state["cap"], floor, k)
        accepted = jnp.minimum(jnp.maximum(_prefix_len(accepts), floor), cap)
        khat = jnp.maximum(jnp.minimum(accepted, remaining), 1)
        # rate tracks the un-clamped acceptance (the budget clamp at the end
        # of a row's generation says nothing about proposal quality)
        rate = (self.decay * state["rate"]
                + (1 - self.decay) * accepted.astype(jnp.float32)
                / cap.astype(jnp.float32))
        cap = jnp.where(rate >= self.grow, jnp.minimum(cap + 1, k),
                        jnp.where(rate <= self.shrink,
                                  jnp.maximum(cap - 1, floor), cap))
        return khat, {"rate": rate, "cap": cap}


# ---------------------------------------------------------------------------
# Drafters
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Drafter:
    """Produces the next block of proposals from the verify forward.

    ``init_state`` sees the decode entry point's inputs (``batch`` — e.g.
    the source sentence for seq2seq, or the padded prompt tokens in the
    serving engine's admission path) and must return a pytree of
    batch-leading ``(b, …)`` arrays, or ``()`` for stateless drafters.
    ``aux`` carries the auxiliary ``ModelBundle`` params when the caller
    has them (decode prefill, engine admission); paths that cannot supply
    params (engine init/evict, ``jax.eval_shape`` struct builders) pass
    ``()`` — model-backed drafters must produce identically-shaped state
    either way.

    ``bind`` attaches the *static* side of the session's auxiliary bundles
    (cfg / kv_chunk / backend factory) to the drafter before any tracing;
    the default is a no-op for drafters that need no second model.
    """

    def init_state(self, cfg, dec: DecodeConfig, batch: Optional[Dict],
                   b: int, aux: Any = ()) -> Any:
        return ()

    def bind(self, bundles: Dict, cfg) -> "Drafter":
        """bundles: {name: core.bundle.ModelBundle}; cfg: the PRIMARY model
        config (for cross-model compatibility checks)."""
        return self

    def tree_topology(self, block_k: int):
        """The static ``kernels.tree_mask.TreeTopology`` this drafter's
        proposals form, or None for chain drafts.  Non-None switches
        ``bpd_iteration`` to tree verification: proposals are node tokens,
        the forward runs under a tree-attention mask, and acceptance picks
        the longest accepted root-to-leaf path."""
        return None

    def draft(self, inputs: DraftInputs, state: Any):
        """-> (proposals (B, k) int32 with slot 0 = verified token, state).
        For tree drafters (``tree_topology`` non-None) slot n is the token
        of tree node n instead of chain slot n."""
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class HeadsDrafter(Drafter):
    """The paper's proposal mechanism: head p_{i+1}'s argmax at the accepted
    slot proposes block slot i (already computed by the verify forward)."""

    def draft(self, inputs: DraftInputs, state: Any):
        return jnp.argmax(inputs.logits, axis=-1), state        # (B, K)


@dataclasses.dataclass(frozen=True)
class InputCopyDrafter(Drafter):
    """Aggressive-Decoding-style drafts for seq2seq: propose the source
    tokens aligned with the next output positions (arXiv:2205.10350).

    On copy-heavy targets (the paper's MT setting; grammar correction;
    our synthetic copy task) the model's greedy output largely *is* the
    source, so source-aligned drafts verify in long blocks even when the
    prediction heads are weak or absent.  Slot 0 stays the verified greedy
    token, so exact acceptance remains lossless on any task.

    ``offset`` shifts the source index for tasks with a known alignment
    offset (output position t reads ``src[t + offset]``).
    """

    offset: int = 0

    def init_state(self, cfg, dec, batch, b, aux=()):
        if batch is None or "src" not in batch:
            raise ValueError(
                "InputCopyDrafter drafts from batch['src'] and is only "
                "meaningful for seq2seq decoding — use HeadsDrafter (or a "
                "custom drafter) for decoder-only models")
        return {"src": jnp.asarray(batch["src"], I32)}

    def draft(self, inputs: DraftInputs, state):
        src = state["src"]
        b, k = inputs.old_proposals.shape
        verified = jnp.argmax(inputs.logits[:, 0], axis=-1)      # p_1 argmax
        # decoder position 0 is BOS, so output index = position - 1; block
        # slot i sits at position text_len + i
        out_idx = (inputs.text_len[:, None] - 1 + self.offset
                   + jnp.arange(k, dtype=I32)[None, :])
        idx = jnp.clip(out_idx, 0, src.shape[1] - 1)
        copied = jnp.take_along_axis(src, idx, axis=1)
        proposals = copied.at[:, 0].set(verified)
        return proposals, state


@dataclasses.dataclass(frozen=True)
class TopKTreeDrafter(Drafter):
    """Drafts a candidate *tree* the verifier scores in one forward (cf.
    arXiv:2404.09221's tree verification): node n at depth d with sibling
    rank r carries head p_{d+1}'s r-th top token at the accepted slot, and
    ``bpd_iteration`` runs the block under a tree-attention mask so p_1's
    logits at every node are conditioned on that node's own ancestor
    chain.  Acceptance then keeps the longest accepted root-to-leaf path —
    with ``block_k`` nodes the forward costs the same as a chain, but the
    verifier gets ``fanout`` shots at the first speculative position
    instead of one.

    The topology is ``kernels.tree_mask.default_tree``: the root (the
    verified greedy token — tree slot 0, k̂ ≥ 1) with ``fanout`` children,
    then a top-1 chain below the rank-0 child, so the classic heads chain
    is always a subtree.  Stateless and lossless under exact acceptance.
    """

    fanout: int = 4

    def bind(self, bundles: Dict, cfg) -> "TopKTreeDrafter":
        if cfg is not None and cfg.is_mla:
            raise NotImplementedError(
                f"{cfg.name}: tree verification is not implemented for "
                f"latent attention (MLA); use a chain policy such as 'exact'")
        return self

    def tree_topology(self, block_k: int):
        from repro.kernels.tree_mask import default_tree

        return default_tree(block_k, self.fanout)

    def draft(self, inputs: DraftInputs, state):
        b, k = inputs.old_proposals.shape
        topo = self.tree_topology(k)
        need = int(topo.ranks.max()) + 1
        _, ids = jax.lax.top_k(inputs.logits, need)              # (B,K,need)
        d = jnp.asarray(topo.depths)                             # head index
        r = jnp.asarray(topo.ranks)                              # rank index
        # node 0 is (depth 0, rank 0) = head p_1's argmax = the verified token
        return ids[:, d, r].astype(I32), state


# ---------------------------------------------------------------------------
# Locality-aware image decoding (arXiv:2507.01957)
# ---------------------------------------------------------------------------


class _LocalityTables(NamedTuple):
    order: np.ndarray          # (H*W,) generation slot -> raster index
    boundaries: np.ndarray     # class-end offsets (block cut points)
    next_boundary: np.ndarray  # (H*W + 1,) smallest boundary > p
    n1: np.ndarray             # (H*W,) committed-neighbor generation index
    n2: np.ndarray
    coarse_len: int            # boundaries[0] — the coarse-lattice prefix


@functools.lru_cache(maxsize=None)
def _locality_tables(height: int, width: int, stride: int) -> _LocalityTables:
    from repro.data.synthetic import locality_plan

    order, bounds, n1, n2 = locality_plan(height, width, stride)
    n = order.size
    nb = np.full(n + 1, n + (1 << 20), np.int64)   # "no boundary left"
    for p in range(n + 1):
        j = int(np.searchsorted(bounds, p, side="right"))
        if j < bounds.size:
            nb[p] = bounds[j]
    return _LocalityTables(order, bounds, nb.astype(np.int32), n1, n2,
                           int(bounds[0]))


@dataclasses.dataclass(frozen=True)
class LocalityDrafter(Drafter):
    """Locality-aware image drafts (arXiv:2507.01957).

    The token stream is an (height, width) raster serialized in the
    progressive-lattice order of ``data.synthetic.locality_plan`` (coarse
    lattice first, then non-adjacent refinement classes), so every
    refinement position has already-committed spatial neighbors — the
    drafter proposes their rounded average (bilinear-style interpolation
    on the ordinal vocabulary) instead of the heads' raster
    extrapolation, then (``window`` > 0) re-ranks the interpolation's
    ±window neighborhood by the verifier's own head logits — the spatial
    prior narrows the candidate set, the heads break the quantization
    rounding ties interpolation cannot see.  State is the committed
    stream in generation order, re-built from each verified block; slot
    0 stays the verified greedy token, so exact acceptance is lossless
    on ANY prompt (drafts change iteration counts, never tokens).
    """

    height: int = 0
    width: int = 0
    stride: int = 4
    window: int = 1

    def init_state(self, cfg, dec, batch, b, aux=()):
        n = self.height * self.width
        k = dec.block_k or getattr(cfg, "bpd_k", 1)
        buf = jnp.zeros((b, n + max(int(k), 1)), I32)
        if batch is not None and "tokens" in batch:
            toks = jnp.asarray(batch["tokens"], I32)[:, :n]
            buf = jax.lax.dynamic_update_slice(buf, toks, (0, 0))
        return {"grid": buf}

    def draft(self, inputs: DraftInputs, state):
        buf = state["grid"]
        b, k = inputs.old_proposals.shape
        cap = buf.shape[1]
        tables = _locality_tables(self.height, self.width, self.stride)
        n1 = jnp.asarray(tables.n1)
        n2 = jnp.asarray(tables.n2)
        # 1. commit the just-verified block into the generation-order buffer.
        #    Slot k̂-1 carries ``prev_token`` (the committed token at
        #    text_len - 1): in loop iterations that equals old_proposals
        #    there, and on the prefill call (old_proposals zeroed, k̂ = 1)
        #    it writes the real last prompt token.
        offs = jnp.arange(k, dtype=I32)[None, :]
        start = inputs.text_len[:, None] - inputs.khat[:, None]
        idx = jnp.clip(start + offs, 0, cap - 1)
        vals = jnp.where(offs == inputs.khat[:, None] - 1,
                         inputs.prev_token[:, None], inputs.old_proposals)
        keep = offs < inputs.khat[:, None]

        def row_commit(row, ix, v, m):
            return row.at[ix].set(jnp.where(m, v, row[ix]))

        buf = jax.vmap(row_commit)(buf, idx, vals.astype(I32), keep)
        # 2. propose: each next position interpolates its committed parents
        pos = jnp.clip(inputs.text_len[:, None] + offs, 0, n1.shape[0] - 1)
        a = jnp.take_along_axis(buf, jnp.clip(n1[pos], 0, cap - 1), axis=1)
        c = jnp.take_along_axis(buf, jnp.clip(n2[pos], 0, cap - 1), axis=1)
        proposals = (a + c + 1) // 2
        if self.window:
            vocab = inputs.logits.shape[-1]
            hl = inputs.logits                              # (B, heads, V)
            hidx = jnp.minimum(jnp.arange(k), hl.shape[1] - 1)
            deltas = jnp.arange(-self.window, self.window + 1, dtype=I32)
            cands = jnp.clip(proposals[..., None] + deltas, 0, vocab - 1)
            scores = jnp.take_along_axis(hl[:, hidx, :], cands, axis=-1)
            pick = jnp.argmax(scores, axis=-1)
            proposals = jnp.take_along_axis(cands, pick[..., None], -1)[..., 0]
        verified = jnp.argmax(inputs.logits[:, 0], axis=-1)      # p_1 argmax
        proposals = proposals.at[:, 0].set(verified)
        return proposals.astype(I32), {"grid": buf}


@dataclasses.dataclass(frozen=True)
class LocalitySchedule(BlockSchedule):
    """Clamps each accepted block at the next offset-class boundary of the
    progressive-lattice order, so a block never commits positions whose
    spatial parents are still uncommitted — and every committed block
    stays spatially non-adjacent within its class.  State: a per-row
    generation cursor starting at ``start`` (the coarse prompt length in
    the canonical image workload; any other prompt length is merely a
    sub-optimal cut alignment, still lossless under exact acceptance)."""

    height: int = 0
    width: int = 0
    stride: int = 4
    start: int = 0

    def init_state(self, b: int) -> Any:
        return {"pos": jnp.full((b,), self.start, I32)}

    def block_size(self, accepts, remaining, state):
        tables = _locality_tables(self.height, self.width, self.stride)
        nb = jnp.asarray(tables.next_boundary)
        pos = state["pos"]
        room = nb[jnp.clip(pos, 0, nb.shape[0] - 1)] - pos
        khat = jnp.minimum(_prefix_len(accepts),
                           jnp.minimum(remaining, room))
        khat = jnp.maximum(khat, 1)
        return khat, {"pos": pos + khat}


# ---------------------------------------------------------------------------
# The composed policy + registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DecodePolicy:
    """Drafter × Acceptor × BlockSchedule behind every decode path."""

    drafter: Drafter
    acceptor: Acceptor
    schedule: BlockSchedule
    name: str = "custom"

    def init_state(self, cfg, dec: DecodeConfig, batch: Optional[Dict],
                   b: int, aux: Any = ()) -> PolicyState:
        return PolicyState(
            drafter=self.drafter.init_state(cfg, dec, batch, b, aux=aux),
            schedule=self.schedule.init_state(b))

    def bind(self, bundles: Dict, cfg) -> "DecodePolicy":
        """Attach the session's auxiliary ``ModelBundle``s (static side:
        cfg / kv_chunk / backend factory) to the drafter.  A no-op for
        single-model policies; model-backed drafters validate and absorb
        their bundle here — BEFORE any tracing — so a missing or
        incompatible draft model fails at session construction."""
        drafter = self.drafter.bind(bundles or {}, cfg)
        if drafter is self.drafter:
            return self
        return dataclasses.replace(self, drafter=drafter)

    @property
    def cache_key(self):
        """Hashable structural identity for jit-cache keying.

        Two policies with equal drafter/acceptor/schedule *parameters*
        (not just equal registry names) share compiled decode entry points
        and serving functions, while ``topk(top_k=2)`` and
        ``topk(top_k=3)`` — same ``name`` — key separately.  Components
        are frozen dataclasses all the way down (a bound drafter's
        ``ModelConfig`` included), reduced here to nested (type, fields)
        tuples so the key is stable across equal-valued instances.
        """
        return policy_cache_key(self)


def policy_cache_key(obj):
    """Reduce a policy (or any of its components) to a hashable tuple.

    Frozen-dataclass components flatten to ``(type, (field, value), ...)``
    recursively; everything else must already be hashable (ints, floats,
    strings, tuples, None, callables)."""
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return (type(obj).__name__,) + tuple(
            (f.name, policy_cache_key(getattr(obj, f.name)))
            for f in dataclasses.fields(obj))
    if isinstance(obj, (list, tuple)):
        return tuple(policy_cache_key(x) for x in obj)
    return obj


# name -> builder(dec) -> DecodePolicy.  The legacy criterion strings are
# aliases for the heads-drafted policies, so ``DecodeConfig.criterion`` and
# ``DecodeConfig.policy`` resolve through the same table.
POLICY_BUILDERS: Dict[str, Callable[[DecodeConfig], DecodePolicy]] = {}


def register_policy(name: str,
                    builder: Callable[[DecodeConfig], DecodePolicy]) -> None:
    if name in POLICY_BUILDERS:
        raise ValueError(f"duplicate policy registration: {name!r}")
    POLICY_BUILDERS[name] = builder


def list_policies() -> list:
    return sorted(POLICY_BUILDERS)


def resolve_policy(dec: DecodeConfig,
                   policy: Union[None, str, DecodePolicy] = None
                   ) -> DecodePolicy:
    """Resolve the policy a decode should run.

    Precedence: an explicit ``DecodePolicy`` object > an explicit name >
    ``dec.policy`` > the legacy ``dec.criterion`` alias.  Builders read
    their knobs (top_k, epsilon, min_block) off ``dec``.
    """
    if isinstance(policy, DecodePolicy):
        return policy
    name = policy or dec.policy or dec.criterion
    builder = POLICY_BUILDERS.get(name)
    if builder is None:
        raise ValueError(f"unknown decode policy {name!r}; "
                         f"registered: {list_policies()}")
    return builder(dec)


def _schedule_for(dec: DecodeConfig) -> BlockSchedule:
    return StaticSchedule(min_block=dec.min_block)


def _maybe_fused(acceptor: Acceptor, dec: DecodeConfig) -> Acceptor:
    """Honor ``DecodeConfig.fused_verify`` in the built-in builders."""
    if getattr(dec, "fused_verify", False):
        return dataclasses.replace(acceptor, fused=True)
    return acceptor


register_policy("exact", lambda dec: DecodePolicy(
    HeadsDrafter(), _maybe_fused(ExactAcceptor(), dec), _schedule_for(dec),
    name="exact"))
register_policy("topk", lambda dec: DecodePolicy(
    HeadsDrafter(), _maybe_fused(TopKAcceptor(top_k=dec.top_k), dec),
    _schedule_for(dec), name="topk"))
register_policy("distance", lambda dec: DecodePolicy(
    HeadsDrafter(), _maybe_fused(DistanceAcceptor(epsilon=dec.epsilon), dec),
    _schedule_for(dec), name="distance"))
register_policy("adaptive", lambda dec: DecodePolicy(
    HeadsDrafter(), _maybe_fused(ExactAcceptor(), dec),
    AdaptiveSchedule(min_block=dec.min_block), name="adaptive"))
register_policy("input_copy", lambda dec: DecodePolicy(
    InputCopyDrafter(), _maybe_fused(ExactAcceptor(), dec), _schedule_for(dec),
    name="input_copy"))
register_policy("topk_tree", lambda dec: DecodePolicy(
    TopKTreeDrafter(fanout=max(dec.top_k, 2)),
    _maybe_fused(ExactAcceptor(), dec), _schedule_for(dec), name="topk_tree"))


def _locality_policy(dec: DecodeConfig) -> DecodePolicy:
    h, w = dec.image_height, dec.image_width
    if h <= 0 or w <= 0:
        raise ValueError(
            "policy 'locality' needs the 2-D raster geometry: set "
            "DecodeConfig.image_height / image_width (and optionally "
            "locality_stride) to the grid shape of the token stream")
    tables = _locality_tables(h, w, dec.locality_stride)
    return DecodePolicy(
        LocalityDrafter(height=h, width=w, stride=dec.locality_stride),
        _maybe_fused(ExactAcceptor(), dec),
        LocalitySchedule(height=h, width=w, stride=dec.locality_stride,
                         start=tables.coarse_len),
        name="locality")


register_policy("locality", _locality_policy)

# the model-backed speculative drafter lives in core.draft (it pulls in the
# model stack); importing it here registers the "draft_model" policy so the
# registry is complete whenever policies are resolvable at all
from repro.core import draft as _draft  # noqa: E402,F401  (registration)
