"""Continuous-batching engine on top of the BPD decode loop.

The run-to-completion ``bpd_decode`` keeps a whole batch resident until its
slowest row finishes — dead rows still cost a model invocation per
iteration.  This engine generalizes ``BPDState`` to a slot-based
``SlotBatch`` (see serving/types.py): a *static* device batch of
``num_slots`` rows where

  * finished rows are evicted (``active`` goes False) and their KV rows are
    invalidated (``pos = -1``) so the slot is immediately reusable,
  * a queued request is admitted mid-flight by a single-row prefill that is
    scattered into the freed slot (``models.cache.scatter_row``) while the
    other slots keep decoding,
  * every slot carries its own prompt length, generation budget and
    statistics, so a decode step is one ``bpd_iteration`` over a slot
    group with a per-slot ``active`` mask and per-slot ``max_new``.

**Per-request decode policies (policy slot grouping).**  The engine's slot
slab is partitioned into per-policy *slot groups*: ``policies={"exact": 2,
"topk_tree": 2}`` gives each named policy its own contiguous range of the
``num_slots`` slab, materialized as a group-local ``SlotBatch`` view with
its own compile-once init/admit/step/evict from
``DecodeSession.serving_fns(policy=...)`` — one jitted step per distinct
(policy, geometry), shared between groups via the session's
``DecodePolicy.cache_key``-keyed jit cache.  An admitted request routes to
the group running its ``Request.policy`` (``None`` = the session default);
the host loop round-robins the active groups each ``step()``, dispatching
every group's step before reading any status back, so device work overlaps
and each *group step* costs exactly ONE fused device→host sync.

The engine itself is a **scheduler + slot-metadata shell**: all device
functions are owned by a ``serving.session.DecodeSession`` — the same
sharding-aware driver behind ``bpd_decode`` — and compile exactly once per
(policy, geometry) (padded prompts, traced slot indices and group ids).
Pass ``mesh=`` (or a prebuilt ``session=``) to shard every group's slot
batch over the data axes and the model over the tensor axis; each group's
slot count must then divide the data axes on its own.

The host loop performs exactly ONE device→host sync per group step: the
jitted step returns a fused (S,) int8 status (bit 0 = active, bit 1 =
harvestable) alongside the donated slot state, and ``free_slots`` /
``has_active`` / a no-finish ``harvest`` read the host-side mirror
(``num_host_syncs`` counts the transfers per GROUP STEP, never per slot —
gated in tests).

Padded prefill is safe because cache visibility is governed by absolute
positions: a stale entry with stored position p is only attended when
``p < length + k``, and the decode step with that length rewrites position
p in ``cache_write`` *before* attending (see models/cache.py).  That
argument covers KV caches only — recurrent-state families (rwkv6 / hymba)
would fold pad tokens into their final state, so the engine is gated to
``block_type == "attn"``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import DecodeConfig, ModelConfig
from repro.serving import tracing
from repro.serving.pages import PageAllocator, PagePoolExhausted
from repro.serving.session import DecodeSession, ServingFns
from repro.serving.types import (EngineConfig, FinishedRequest,
                                 PreemptedRequest, Request, SlotBatch)

__all__ = ["ContinuousBatchingEngine", "PolicyGroup", "SlotBatch",
           "PagePoolExhausted", "PreemptedRequest"]

I32 = jnp.int32


@dataclasses.dataclass
class PolicyGroup:
    """Host-side record of one policy slot group: a contiguous view of the
    engine's slot slab ([offset, offset + num_slots)) stepped by its own
    compiled functions under its own decode policy."""

    gid: int                    # group index (== SlotBatch.group rows)
    name: str                   # registered policy name (routing key)
    policy: object              # the bound DecodePolicy
    offset: int                 # first global slot id of this group
    num_slots: int              # slots in this group's view
    fns: ServingFns             # compiled init/admit/step/evict
    state: SlotBatch            # the group-local device state
    status: np.ndarray          # host mirror, (num_slots,) int8
    slot_meta: List[Optional[dict]]
    pages: Optional[PageAllocator] = None  # host page allocator (paged only)

    def free_local(self) -> List[int]:
        """Group-local indices of free slots (host mirror, bit 0 clear) —
        the one definition of "free" shared by admission and the engine's
        global free-slot view."""
        return [i for i in range(self.num_slots) if not self.status[i] & 1]


def _normalize_groups(policies, default_name: str,
                      num_slots: int) -> List[Tuple[str, int]]:
    """policies: None | {name: slots} | [(name, slots), ...] -> ordered
    [(name, slots)] partitioning ``num_slots``."""
    if policies is None:
        return [(default_name, num_slots)]
    items = (list(policies.items()) if isinstance(policies, dict)
             else [tuple(p) for p in policies])
    if not items:
        raise ValueError("policies must name at least one slot group")
    names = [n for n, _ in items]
    if len(set(names)) != len(names):
        raise ValueError(f"duplicate policy group names in {names}: one "
                         f"slot group per policy")
    for n, sl in items:
        if sl <= 0:
            raise ValueError(f"policy group {n!r} has {sl} slots: every "
                             f"group needs at least one")
    total = sum(sl for _, sl in items)
    if total != num_slots:
        raise ValueError(
            f"policy groups {dict(items)} cover {total} slots but "
            f"EngineConfig.num_slots={num_slots}: groups must partition "
            f"the slot slab exactly")
    return items


class ContinuousBatchingEngine:
    """Slot-based continuous batching for the decoder-only BPD loop,
    with per-request decode policies via policy slot groups."""

    def __init__(self, params, cfg: ModelConfig, dec: DecodeConfig,
                 ecfg: EngineConfig, *, mesh=None,
                 session: Optional[DecodeSession] = None, policy=None,
                 bundles=None,
                 policies: Union[None, Dict[str, int],
                                 Sequence[Tuple[str, int]]] = None):
        if cfg.block_type != "attn":
            raise NotImplementedError(
                f"serving engine requires an attention-cache family "
                f"(block_type='attn'), got {cfg.block_type!r}: recurrent "
                f"states cannot be prefilled from a padded prompt")
        if cfg.modality != "text":
            raise NotImplementedError(
                "serving engine v1 is text-only (per-request vision prefixes "
                "would make the prefill shape dynamic)")
        if cfg.is_encoder_only or cfg.is_encoder_decoder:
            raise NotImplementedError("serving engine is decoder-only")

        self.session = session if session is not None else DecodeSession(
            params, cfg, dec, mesh=mesh, policy=policy, bundles=bundles)
        for name, b in self.session.bundles.items():
            if b.cfg.block_type != "attn":
                raise NotImplementedError(
                    f"auxiliary bundle {name!r} has block_type="
                    f"{b.cfg.block_type!r}: the engine's padded admission "
                    f"prefill is only sound for attention caches (same "
                    f"argument as the primary model)")
        ecfg.validate(dec=self.session.dec, mesh=self.session.mesh)
        self.policy = self.session.policy

        # the session is the source of truth for model/decode config — a
        # caller-provided session may differ from the cfg/dec args, and the
        # device functions are built from the session's copies
        self.cfg = cfg = self.session.cfg
        self.dec = dec = self.session.dec
        self.ecfg = ecfg
        self.block_k = dec.block_k or cfg.bpd_k
        self.prefix = cfg.num_meta_tokens
        self.context_len = self.prefix + ecfg.max_prompt_len + ecfg.max_new_cap
        self.buf_len = ecfg.max_prompt_len + ecfg.max_new_cap + self.block_k

        # -- policy slot groups: partition the slab, one compiled fns set
        # per distinct (policy, geometry), one state view per group --------
        self.default_policy = self.policy.name
        specs = _normalize_groups(policies, self.default_policy,
                                  ecfg.num_slots)
        self.groups: List[PolicyGroup] = []
        offset = 0
        for gid, (name, slots) in enumerate(specs):
            gecfg = dataclasses.replace(ecfg, num_slots=slots)
            # per-group mesh validation: each group's view shards the data
            # axes on its own, so every group's slot count must divide them
            gecfg.validate(dec=dec, mesh=self.session.mesh)
            # the default group (policies=None) serves the session's BOUND
            # policy object — re-resolving its name through the registry
            # would silently replace a caller-supplied / hand-built
            # DecodePolicy with the registry default of the same name
            pol_arg = None if policies is None else name
            fns = self.session.serving_fns(gecfg, policy=pol_arg)
            # each group owns its page pool (its SlotBatch holds a separate
            # kp/vp buffer), so the host allocator is per-group too
            pages = None
            if fns.paged is not None:
                geom = fns.paged
                pages = PageAllocator(geom.num_pages, geom.page_size,
                                      geom.pages_per_row,
                                      prefix_len=geom.prefix_len)
            self.groups.append(PolicyGroup(
                gid=gid, name=name,
                policy=self.session.bound_policy(pol_arg),
                offset=offset, num_slots=slots, fns=fns,
                state=fns.init(jnp.asarray(gid, I32)),
                status=np.zeros((slots,), np.int8),
                slot_meta=[None] * slots,
                pages=pages))
            offset += slots
        self._by_name = {g.name: g for g in self.groups}
        self._rr = 0            # round-robin pointer over group steps

        self.num_admits = 0     # requests admitted into a slot
        self.num_steps = 0      # decode ITERATIONS (full-width model
                                # forwards; a windowed step adds every
                                # iteration its while_loop actually ran)
        self.num_host_syncs = 0  # device->host readbacks (regression guard)
        self.num_stream_syncs = 0  # poll_progress readbacks (streaming only)
        # host time per phase is in the ``serve.engine.*`` spans
        # (serving/tracing.py); ``_gap_from`` is when the last status pull
        # returned, while a device program is still to be dispatched after
        # it: the start of the host gap ``serve.engine.host_gap`` counts
        self._gap_from: Optional[float] = None
        # harvest of one group completed while ANOTHER stepped group's
        # status was still unpulled (its device step still in flight) —
        # the async per-group stream overlap, asserted in tests
        self.num_overlap_harvests = 0

    @property
    def params(self):
        """Mesh-placed parameters (owned by the DecodeSession)."""
        return self.session.params

    @property
    def aux_params(self):
        """Auxiliary bundle params (e.g. the draft model's), mesh-placed
        per bundle by the DecodeSession."""
        return self.session.aux_params

    @property
    def state(self) -> SlotBatch:
        """The slot state — single-group engines only (the historical
        engine API).  Multi-group engines expose per-group views via
        ``groups`` / ``group_for``."""
        if len(self.groups) != 1:
            raise AttributeError(
                f"engine has {len(self.groups)} policy slot groups — read "
                f"engine.groups[gid].state (or group_for(policy).state) "
                f"instead of the single-group .state shorthand")
        return self.groups[0].state

    # -- group routing -------------------------------------------------------

    def group_for(self, policy: Optional[str]) -> PolicyGroup:
        """The slot group serving ``policy`` (None = the session default).
        Raises ValueError for policies the engine was not configured with,
        resolving the name through ``config.registry`` first so unknown
        names fail with the registry's message."""
        name = policy or self.default_policy
        g = self._by_name.get(name)
        if g is None:
            from repro.config import get_policy

            get_policy(self.dec, name)  # unknown name -> registry ValueError
            raise ValueError(
                f"request policy {name!r} has no slot group in this engine "
                f"(groups: {sorted(self._by_name)}): configure it via "
                f"ContinuousBatchingEngine(policies={{{name!r}: n, ...}})")
        return g

    def policy_names(self) -> List[str]:
        return [g.name for g in self.groups]

    # -- host-side API -------------------------------------------------------

    def free_slots(self, policy: Optional[str] = None) -> List[int]:
        """Global ids of free slots — all groups (default), or the single
        group serving ``policy`` (a name; pass the default policy's name
        to query the default group alone)."""
        groups = self.groups if policy is None else [self.group_for(policy)]
        return [g.offset + i for g in groups for i in g.free_local()]

    def has_active(self) -> bool:
        return any(bool(np.any(g.status & 1)) for g in self.groups)

    def _padded(self, req: Request) -> Tuple[np.ndarray, int, np.ndarray, int]:
        """Pad a request's prompt/src rows to the admission geometry."""
        p = len(req.prompt)
        if not 0 < p <= self.ecfg.max_prompt_len:
            raise ValueError(
                f"prompt length {p} outside (0, {self.ecfg.max_prompt_len}]")
        prompt = np.zeros((self.ecfg.max_prompt_len,), np.int32)
        prompt[:p] = req.prompt
        # source tokens for drafting policies: the request's src (padded /
        # truncated to the admission geometry), defaulting to the prompt
        src_toks = req.prompt if req.src is None else req.src
        src = np.zeros((self.ecfg.max_prompt_len,), np.int32)
        n_src = min(len(src_toks), self.ecfg.max_prompt_len)
        src[:n_src] = src_toks[:n_src]
        max_new = int(np.clip(req.max_new, 1, self.ecfg.max_new_cap))
        return prompt, p, src, max_new

    def admit(self, req: Request, *, now: Optional[float] = None) -> int:
        """Admit a request into a free slot of its policy's group; returns
        the global slot index."""
        with tracing.span("serve.engine.admit", rid=req.rid):
            return self._admit(req, now)

    def _admit(self, req: Request, now: Optional[float]) -> int:
        with tracing.span("serve.engine.admit_prep", rid=req.rid):
            g = self.group_for(req.policy)
            free = g.free_local()
            if not free:
                raise RuntimeError(
                    f"no free slot in policy group {g.name!r} — poll "
                    f"step()/harvest first")
            slot = free[0]
            prompt, p, src, max_new = self._padded(req)
            extra = ()
            if g.pages is not None:
                # host-side page plan first: raises PagePoolExhausted
                # (back-pressure, the scheduler requeues) before any device
                # work, and reuses pooled pages for identical prompt
                # prefixes (CoW)
                tbl_row, write_mask = g.pages.plan_admit(
                    slot, req.prompt, p, max_new, self.block_k)
                extra = (jnp.asarray(tbl_row), jnp.asarray(write_mask))
            args = (jnp.asarray(slot, I32), jnp.asarray(prompt),
                    jnp.asarray(p, I32), jnp.asarray(max_new, I32),
                    jnp.asarray(src)) + extra
        g.state = g.fns.admit(self.params, self.aux_params, g.state, *args)
        self._dispatched()
        g.status[slot] = 1          # known host-side: no readback needed
        self.num_admits += 1
        admit_time = time.monotonic() if now is None else now
        if req.arrival is None:
            req.arrival = admit_time
        g.slot_meta[slot] = {
            "req": req, "prompt_len": p, "max_new": max_new,
            "admit_time": admit_time, "emitted": 0,
        }
        return g.offset + slot

    def step(self, *, now: Optional[float] = None) -> List[FinishedRequest]:
        """One BPD iteration over every active slot group, then
        harvest+evict.

        Async per-group streams: groups step round-robin (the starting
        group rotates so no policy is systematically served first), ALL
        group steps are dispatched before any status is read back, and
        each stepped group is then pulled AND harvested in dispatch order
        — so the host-side harvest of group A (status pull, token copies,
        retirement, evict dispatch) overlaps group B's still-in-flight
        device step (counted in ``num_overlap_harvests``).  Each group
        step still costs exactly one fused device→host sync, now off the
        critical path of the other groups' device work.
        """
        n = len(self.groups)
        order = [self.groups[(self._rr + i) % n] for i in range(n)]
        self._rr = (self._rr + 1) % n
        stepped = []
        with tracing.span("serve.engine.dispatch"):
            for g in order:
                if not np.any(g.status & 1):
                    continue                 # idle group: no device work
                g.state, status, iters = g.fns.step(self.params,
                                                    self.aux_params, g.state)
                stepped.append((g, status, iters))
        if stepped:
            self._dispatched()
        # the ONE per-group-step device->host round-trip: a fused (S,) int8
        # array carrying both the active and the finished bits (the harvest
        # decision) — pulled only after every group's step is in flight,
        # and each group's harvest runs before the NEXT group's pull
        out: List[FinishedRequest] = []
        synced = None
        for idx, (g, status, iters) in enumerate(stepped):
            # one fused pull: the (S,) status plus the window's iteration
            # count (a windowed step is 1..steps_per_sync forwards — the
            # invocation accounting must count every one of them)
            with tracing.span("serve.engine.sync"):
                status_h, it = jax.device_get((status, iters))
            synced = time.perf_counter()
            g.status = np.array(status_h)    # writable host copy
            self.num_steps += int(it)
            self.num_host_syncs += 1
            out += self._harvest_group(g, now=now)
            if idx < len(stepped) - 1:
                # host work above ran while the later stepped groups'
                # statuses were still unpulled (their device steps free to
                # proceed) — the measurable async-stream overlap
                self.num_overlap_harvests += 1
        # the device idles from here until the host dispatches its next
        # program; with no active slot left, waiting is the traffic's
        self._gap_from = synced if self.has_active() else None
        return out

    def _dispatched(self) -> None:
        """An admission or a step was just dispatched: close the host gap
        that the last status pull opened."""
        if self._gap_from is not None:
            tracing.record("serve.engine.host_gap",
                           time.perf_counter() - self._gap_from)
            self._gap_from = None

    def harvest(self, *, now: Optional[float] = None) -> List[FinishedRequest]:
        """Retire finished slots of every group: copy outputs out, free
        the slots (host-cached status decides — a no-finish group costs
        zero additional device syncs)."""
        out: List[FinishedRequest] = []
        for g in self.groups:
            out += self._harvest_group(g, now=now)
        return out

    def _harvest_group(self, g: PolicyGroup, *,
                       now: Optional[float] = None) -> List[FinishedRequest]:
        """Retire the finished slots of ONE group.

        Decides from the host-cached status — the common no-finish group
        step costs zero additional device syncs; the big per-slot arrays
        are only pulled when something actually finished (one pull per
        finishing group, counted in ``num_host_syncs``).
        """
        done_mask = (g.status & 2).astype(bool)
        n = int(done_mask.sum())
        with tracing.span("serve.engine.harvest", n=n):
            return self._retire(g, done_mask, now) if n else []

    def _retire(self, g: PolicyGroup, done_mask: np.ndarray,
                now: Optional[float]) -> List[FinishedRequest]:
        t = time.monotonic() if now is None else now
        out: List[FinishedRequest] = []
        # one FUSED transfer for all four arrays — a single host round-trip
        # instead of four sequential blocking pulls
        tokens, text_len, generated, invocations = jax.device_get(
            (g.state.tokens, g.state.text_len,
             g.state.generated, g.state.invocations))
        self.num_host_syncs += 1  # one harvest pull per finishing group
        for i in np.nonzero(done_mask)[0]:
            meta = g.slot_meta[i]
            req: Request = meta["req"]
            p = meta["prompt_len"]
            iters = max(int(invocations[i]) - 1, 1)  # minus the prefill
            out.append(FinishedRequest(
                rid=req.rid, prompt_len=p,
                tokens=tokens[i, p:int(text_len[i])].copy(),
                generated=int(generated[i]),
                invocations=int(invocations[i]),
                mean_accepted=float(generated[i]) / iters,
                arrival=req.arrival, admit_time=meta["admit_time"],
                finish_time=t, policy=g.name))
            g.slot_meta[i] = None
            if g.pages is not None:
                g.pages.release(int(i))
        g.state = g.fns.evict(g.state, jnp.asarray(done_mask))
        g.status[done_mask] = 0     # known host-side: freed, inactive
        return out

    # -- streaming + preemption (serving front end) --------------------------

    def poll_progress(self) -> List[Tuple[Request, np.ndarray]]:
        """Committed-but-unstreamed tokens per ACTIVE slot since the last
        poll: ``[(request, new_tokens), ...]``.

        This is the streaming read the HTTP/SSE front end runs after each
        ``step()``; it costs one extra device→host pull per group with
        active slots (counted in ``num_stream_syncs``, separate from the
        engine's one-fused-sync-per-group-step contract — callers that
        never stream never pay it).  A slot that finished in the preceding
        step was already harvested (its meta is gone); its tail tokens
        reach the front end through ``FinishedRequest.tokens`` instead.
        """
        out: List[Tuple[Request, np.ndarray]] = []
        with tracing.span("serve.engine.poll"):
            for g in self.groups:
                live = [i for i in range(g.num_slots)
                        if (g.status[i] & 1) and g.slot_meta[i] is not None]
                if not live:
                    continue
                with tracing.span("serve.engine.poll_sync"):
                    tokens, text_len = jax.device_get(
                        (g.state.tokens, g.state.text_len))
                self.num_stream_syncs += 1
                for i in live:
                    meta = g.slot_meta[i]
                    start = meta["prompt_len"] + meta["emitted"]
                    end = int(text_len[i])
                    if end > start:
                        out.append((meta["req"],
                                    tokens[i, start:end].copy()))
                        meta["emitted"] = end - meta["prompt_len"]
        return out

    def pull_group(self, g: PolicyGroup) -> Tuple[np.ndarray, np.ndarray,
                                                  np.ndarray, np.ndarray]:
        """One host pull of group ``g``'s per-slot progress arrays
        ``(tokens, text_len, generated, invocations)`` — the scheduler
        reads these to pick a preemption victim (feasibility needs
        ``generated``), then hands them back to ``preempt`` so choosing
        and evicting cost a single sync together."""
        pulled = (np.asarray(g.state.tokens), np.asarray(g.state.text_len),
                  np.asarray(g.state.generated),
                  np.asarray(g.state.invocations))
        self.num_host_syncs += 1
        return pulled

    def preempt(self, g: PolicyGroup, slot: int,
                pulled=None) -> PreemptedRequest:
        """Evict the ACTIVE request in group ``g``'s local ``slot`` and
        return its committed progress for requeueing.

        Mirrors harvest's cleanup exactly (evict + page release + status/
        meta clear) but for one mid-flight slot: the committed tokens
        survive in the returned record, uncommitted block proposals are
        discarded (they live beyond ``text_len`` and were never part of
        the result stream).  The caller (scheduler) re-admits the request
        as a continuation whose prompt is ``prompt + tokens`` — the same
        padded-prefill path as any admission, so the continuation's stream
        is the decode of the identical committed context.

        ``pulled`` is an optional ``pull_group(g)`` result to reuse (victim
        selection already paid the sync); None pulls fresh.
        """
        if not g.status[slot] & 1 or g.slot_meta[slot] is None:
            raise RuntimeError(
                f"preempt: slot {slot} of group {g.name!r} holds no active "
                f"request")
        tokens, text_len, generated, invocations = (
            pulled if pulled is not None else self.pull_group(g))
        meta = g.slot_meta[slot]
        rec = PreemptedRequest(
            req=meta["req"],
            tokens=tokens[slot, meta["prompt_len"]:int(text_len[slot])].copy(),
            generated=int(generated[slot]),
            invocations=int(invocations[slot]),
            streamed=meta["emitted"])
        mask = np.zeros((g.num_slots,), bool)
        mask[slot] = True
        g.state = g.fns.evict(g.state, jnp.asarray(mask))
        g.status[slot] = 0
        g.slot_meta[slot] = None
        if g.pages is not None:
            g.pages.release(slot)
        return rec

    # -- diagnostics ---------------------------------------------------------

    def compile_counts(self) -> dict:
        """jit cache sizes — the recompilation regression guard.  Each entry
        must be ≤ 1 after any amount of traffic (static shapes by design).
        Distinct (policy, geometry) fns are counted once even when several
        groups share them (the session's jit cache dedups); multi-group
        engines prefix entries with the policy name."""
        single = len(self.groups) == 1
        out, seen = {}, set()
        for g in self.groups:
            if id(g.fns) in seen:
                continue
            seen.add(id(g.fns))
            for part in ("admit", "step", "evict"):
                n = getattr(g.fns, part)._cache_size()
                if n == 0:
                    # a group that never ran this function (no request
                    # reached it) has nothing that could have recompiled
                    continue
                key = part if single else f"{g.name}/{part}"
                out[key] = n
        return out
