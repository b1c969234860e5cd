"""Request/response and configuration types for the continuous-batching
BPD serving engine, plus the device-side ``SlotBatch`` state.

A ``Request`` is one decode job (prompt + generation budget).  The engine
holds ``EngineConfig.num_slots`` requests in flight at once; finished slots
are evicted and refilled from the scheduler queue without recompiling
(static batch shape, per-slot active mask).
"""
from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Any, NamedTuple, Optional

import numpy as np

if TYPE_CHECKING:  # pragma: no cover - annotations only
    import jax.numpy as jnp


class SlotBatch(NamedTuple):
    """Device-side state: ``BPDState`` generalized to reusable slots.

    The slot dimension IS the decode batch dimension — under a mesh it
    shards over the data axes (``sharding.policy.slot_specs``) exactly like
    a static decode batch, and admission/eviction stay slot-local scatters.

    With per-request decode policies the engine's slot slab is partitioned
    into per-policy *slot groups*; each group's ``SlotBatch`` is the
    group-local view of the slab (its ``group`` field records which group
    the rows belong to), stepped by that group's own compiled functions.
    """

    tokens: "jnp.ndarray"      # (S, buf) per-slot prompt+output buffer
    text_len: "jnp.ndarray"    # (S,) valid tokens in the buffer
    prompt_len: "jnp.ndarray"  # (S,) prompt portion of text_len
    proposals: "jnp.ndarray"   # (S, k) next-block proposals
    caches: Any                # per-layer cache pytree (batch dim = S)
    active: "jnp.ndarray"      # (S,) bool — slot holds a live request
    finished: "jnp.ndarray"    # (S,) bool — request hit EOS / budget
    generated: "jnp.ndarray"   # (S,) accepted tokens so far
    max_new: "jnp.ndarray"     # (S,) per-slot generation budget
    invocations: "jnp.ndarray" # (S,) model calls spent on this request
    policy_state: Any = ()     # per-slot DecodePolicy state (batch-leading
                               # leaves; reset on admit/evict)
    group: Any = ()            # (S,) int32 policy slot-group id: stamps
                               # every device-side state dump with the
                               # group that owns it (asserted in the
                               # equivalence tests), and is the routing
                               # key a future policy-batched step would
                               # switch on device-side


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static shapes of the serving engine (fixed at compile time)."""

    num_slots: int = 4          # concurrent requests in the device batch
    max_prompt_len: int = 32    # prompts are padded to this for admission
    max_new_cap: int = 64       # hard per-request generation budget
    page_pool_pages: int = 0    # paged backend: physical pages in the pool
                                # (incl. the trash page); 0 = auto worst
                                # case (1 + num_slots * pages_per_slot)
    steps_per_sync: int = 1     # fused decode iterations per step()
                                # dispatch: >1 runs up to this many masked
                                # iterations in ONE device call (a bounded
                                # while_loop over the same traced step
                                # body, so tokens are identical by
                                # construction) that exits early the
                                # moment any row finishes — slot refill
                                # timing is unchanged, only arrival
                                # admission is delayed by at most
                                # steps_per_sync - 1 iterations.  Trades
                                # bounded admission staleness for
                                # static-batching dispatch economy; 1 =
                                # one iteration per sync (the historical
                                # path)

    def validate(self, dec=None, mesh=None) -> None:
        """Fail construction-time with a clear message instead of a
        downstream shape/trace error.

        dec  : optional DecodeConfig — ``max_new_cap`` must fit inside its
               ``max_new_tokens`` loop bound; its ``cache_backend`` /
               ``page_size`` gate the page-pool geometry checks.
        mesh : optional jax Mesh — the slot batch shards over the data
               axes, so ``num_slots`` must split evenly across them.
        """
        if self.num_slots <= 0:
            raise ValueError(
                f"EngineConfig.num_slots must be positive, got "
                f"{self.num_slots}")
        if self.max_prompt_len <= 0:
            raise ValueError(
                f"EngineConfig.max_prompt_len must be positive, got "
                f"{self.max_prompt_len}")
        if self.max_new_cap <= 0:
            raise ValueError(
                f"EngineConfig.max_new_cap must be positive, got "
                f"{self.max_new_cap}")
        if self.steps_per_sync < 1:
            raise ValueError(
                f"EngineConfig.steps_per_sync must be >= 1, got "
                f"{self.steps_per_sync}")
        if dec is not None and self.max_new_cap > dec.max_new_tokens:
            raise ValueError(
                f"EngineConfig.max_new_cap={self.max_new_cap} exceeds "
                f"DecodeConfig.max_new_tokens={dec.max_new_tokens}: the "
                f"decode loop bound would truncate requests below their "
                f"advertised budget")
        if dec is not None and getattr(dec, "cache_backend", "dense") == "paged":
            ps = dec.page_size
            if ps <= 0 or ps % 8 != 0:
                raise ValueError(
                    f"DecodeConfig.page_size={ps} must be a positive "
                    f"multiple of 8: KV pages tile the TPU sublane dim, and "
                    f"a non-multiple fragments every page scatter/gather")
            if self.page_pool_pages:
                # lower bound on pages one max-size request maps (the true
                # span adds the model prefix and decode block slack, which
                # the session knows; validation uses what it can see)
                per_slot = -(-(self.max_prompt_len + self.max_new_cap) // ps)
                if self.page_pool_pages < 1 + per_slot:
                    raise ValueError(
                        f"EngineConfig.page_pool_pages={self.page_pool_pages}"
                        f" cannot admit even one request: a max-size request "
                        f"maps >= ceil((max_prompt_len + max_new_cap) / "
                        f"page_size) = ceil(({self.max_prompt_len} + "
                        f"{self.max_new_cap}) / {ps}) = {per_slot} pages, "
                        f"plus the reserved trash page 0.  Raise "
                        f"page_pool_pages to at least {1 + per_slot} (or to "
                        f"1 + num_slots * pages_per_slot = "
                        f"{1 + self.num_slots * per_slot} to rule out "
                        f"admission back-pressure entirely; 0 auto-sizes to "
                        f"the worst case)")
        if mesh is not None:
            from repro.sharding.policy import batch_axes, data_axis_size

            # batch_axes is the single source of truth for how the slot
            # batch shards (it already falls back from pod×data to data
            # alone) — reject only configurations it cannot shard at all,
            # which would silently replicate the whole slot batch.
            dsz = data_axis_size(mesh)
            if dsz > 1 and batch_axes(mesh, self.num_slots) is None:
                raise ValueError(
                    f"EngineConfig.num_slots={self.num_slots} is not "
                    f"divisible by the mesh data axes (data-axis product "
                    f"{dsz}, mesh axes {dict(mesh.shape)}): the slot "
                    f"batch cannot shard and would be replicated — pick "
                    f"num_slots as a multiple of the data axis size")


@dataclasses.dataclass
class Request:
    """One decode job submitted to the scheduler.

    ``arrival`` is an absolute ``time.monotonic()`` instant; ``None`` means
    "now" — the scheduler (or engine, for direct admission) stamps it, so
    latency = finish - arrival is always well-defined.

    ``policy`` names the registered decode policy this request wants
    (resolved through ``config.registry``); ``None`` means the engine's
    session default.  The engine serves a request from the slot group
    running its policy, so only policies the engine was configured with
    are admissible.

    ``src`` optionally carries source tokens for source-drafting policies
    (``input_copy``); ``None`` defaults to the prompt itself at admission.
    Drafts never change accepted tokens under exact acceptance, so ``src``
    only moves iteration counts.

    ``priority`` orders admission (higher = served first within a group);
    ``deadline`` is an absolute ``time.monotonic()`` instant by which the
    request should FINISH.  A queued request whose deadline is at risk may
    preempt a strictly-lower-priority slot in its group (the victim is
    evicted and requeued as a continuation — see ``serving.scheduler``).
    Both default to best-effort (priority 0, no deadline), which preserves
    the historical fcfs/sjf behavior exactly.
    """

    rid: int
    prompt: np.ndarray          # (P,) int32 token ids, P <= max_prompt_len
    max_new: int                # requested tokens, clamped to max_new_cap
    arrival: Optional[float] = None
    policy: Optional[str] = None  # registered policy name; None = default
    src: Optional[np.ndarray] = None  # source tokens for drafting policies
    priority: int = 0           # admission priority (higher wins)
    deadline: Optional[float] = None  # absolute finish deadline (monotonic)
    backpressured: int = 0      # times requeued by PagePoolExhausted

    def __post_init__(self):
        self.prompt = np.asarray(self.prompt, np.int32).reshape(-1)
        if self.src is not None:
            self.src = np.asarray(self.src, np.int32).reshape(-1)


@dataclasses.dataclass
class FinishedRequest:
    """A retired request with its serving statistics."""

    rid: int
    prompt_len: int
    tokens: np.ndarray          # generated tokens only (no prompt)
    generated: int              # accepted tokens
    invocations: int            # model calls spent (prefill + iterations)
    mean_accepted: float        # k̂ for this request (generated / iterations)
    arrival: float
    admit_time: float
    finish_time: float
    policy: str = ""            # decode policy that served this request
    preempted: int = 0          # times this request was evicted + requeued

    @property
    def latency(self) -> float:
        return self.finish_time - self.arrival

    @property
    def queue_delay(self) -> float:
        return self.admit_time - self.arrival


@dataclasses.dataclass
class PreemptedRequest:
    """A mid-flight request evicted from its slot by the scheduler.

    ``tokens`` are the committed tokens of the evicted SEGMENT only (the
    continuation re-admits with ``prompt + tokens`` as its prompt, so the
    decode stream continues exactly where it stopped); ``streamed`` counts
    how many of them the engine's progress polling already emitted, so a
    streaming front end can forward the unstreamed remainder before the
    continuation produces new tokens.
    """

    req: "Request"              # the evicted request (original fields)
    tokens: np.ndarray          # committed tokens of this segment
    generated: int              # == len(tokens)
    invocations: int            # model calls spent on this segment
    streamed: int               # tokens of this segment already streamed


def percentile(values, q: float) -> Optional[float]:
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))
