"""Mesh-sharded decode sessions: one sharding-aware driver for every decode
entry point.

A ``DecodeSession`` owns the model parameters (device_put with
``sharding.policy.param_shardings`` when a mesh is given) and the jitted
decode functions, each built **once** per geometry from explicit
``in_shardings`` / ``out_shardings``:

  * run-to-completion — ``decode`` (bpd), ``greedy``, ``decode_seq2seq``:
    the loop-carried ``BPDState`` / ``GreedyState`` is pinned with
    ``sharding.policy.state_specs`` (batch over the data axes, caches via
    ``cache_specs`` — kv-heads or buffer length over ``model``), so GSPMD
    keeps it partitioned through the whole ``while_loop``.
  * serving — ``serving_fns(ecfg)`` returns the engine's compile-once
    ``init`` / ``admit`` / ``step`` / ``evict`` with ``SlotBatch`` pinned by
    ``slot_specs`` and the loop-carried state **donated** (``donate_argnums``)
    so HBM never holds two copies of the KV buffers between steps.
    Admission is a global scatter under a sharding constraint: the padded
    single-row prefill is replicated, then written into the batch-sharded
    slot buffers as a masked local write on the owning data shard.

Placement modes:

  * ``mesh=None`` (default): trace-transparent local mode — identical to
    the historical eager paths, safe under an outer ``jax.jit``.
  * ``mesh=None, jit=True``: compile-once entry points without placement
    (the static-batch benchmark baseline).
  * ``mesh=Mesh(..., ("data", "model"))``: fully sharded — Megatron-style
    tensor parallelism over ``model``, batch/slot parallelism over
    ``data`` (+ ``pod``).

All three decode entry points in ``core.decode`` and the
``ContinuousBatchingEngine`` run through this one session layer, so the
static-batch paper baselines and continuous batching share a single
driver.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config import DecodeConfig, ModelConfig
from repro.core import decode as decode_lib
from repro.core import policy as policy_lib
from repro.models import cache as cache_lib
from repro.models import model as model_lib
from repro.serving.types import EngineConfig, SlotBatch
from repro.sharding import policy as sharding_policy

I32 = jnp.int32


class PagedGeometry(NamedTuple):
    """Static page-pool geometry of a serving slot group — everything the
    engine's host-side ``serving.pages.PageAllocator`` needs to mirror the
    device block tables."""

    page_size: int      # tokens per KV page
    pages_per_row: int  # block-table width P
    num_pages: int      # physical pool size (incl. trash page 0)
    prefix_len: int     # model prefix (meta tokens) before the prompt


def _structs(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def _geometry(batch: Dict) -> tuple:
    return tuple(sorted((k, tuple(v.shape), str(v.dtype))
                        for k, v in batch.items()))


class PrefillPacket(NamedTuple):
    """Finished prefill state for a batch of prompts, before any slot is
    chosen — the unit of work a prefill worker hands to a decode group
    through the engine's KV-handoff queue.

    Every leaf leads with the prefill width ``W``; row ``i`` is one
    request's complete admission state (token buffer, first-block
    proposals, prefilled KV caches, fresh per-row policy state).  A packet
    is slot-independent by construction: ``attach`` scatters one row into
    any free slot later, so prefill never serializes behind a decode step.
    Under a pod mesh the packet shards its rows over the ``pod`` axis
    (``sharding.policy.packet_specs``) — the attach-time resharding into
    the ("pod", "data")-sharded slot slab IS the prefill→decode KV
    handoff transfer.
    """

    tokens: Any        # (W, buf_len) slot token buffer rows (padded prompt)
    prompt_len: Any    # (W,) real prompt lengths
    proposals: Any     # (W, k) first-block draft proposals
    caches: Any        # prefilled KV caches, batch dim = W (row workspace)
    policy_state: Any  # fresh per-row DecodePolicy state (W-leading leaves)


class ServingFns(NamedTuple):
    """The engine's device functions, compiled once per (policy, geometry).

    ``aux`` is the session's {bundle name: params} dict of auxiliary
    models (empty for single-model sessions); it rides along wherever the
    decode policy may run a model of its own.  ``init`` takes the policy
    slot-group id (traced, so every group of the same policy and geometry
    shares one compiled function); ``admit`` additionally takes the
    request's source tokens (padded like the prompt) for source-drafting
    policies.

    ``admit`` IS ``attach ∘ prefill`` at width 1: the unified engine's
    admission and the disaggregated engine's prefill-worker path trace the
    same prefill body and the same scatter, so the two modes are
    token-identical by construction rather than by test alone.
    """

    init: Callable      # (gid) -> SlotBatch (mesh-placed when sharded)
    admit: Callable     # (params, aux, state, slot, prompt, plen, max_new,
                        #  src[, tbl_row, write_mask]) -> state — the two
                        # trailing page-mapping args exist iff paged
    step: Callable      # (params, aux, state) -> (state, status (S,) int8)
    evict: Callable     # (state, mask) -> state
    prefill: Callable   # (params, aux, prompts (W,P), plens (W,),
                        #  srcs (W,P)) -> PrefillPacket — the slot-free
                        # half of admission, batched to the prefill width
    attach: Callable    # (state, packet, row, slot, max_new
                        #  [, tbl_row, write_mask]) -> state — the
                        # scatter-only half (the KV handoff)
    attach_many: Callable = None  # (state, packet, rows (W,), slots (W,),
                        #  max_news (W,), valid (W,)[, tbl_rows (W,P),
                        #  write_masks (W,P)]) -> state — up to W handoffs
                        # in ONE dispatch (invalid lanes write nothing)
    paged: Optional["PagedGeometry"] = None  # page-pool geometry (None=dense)


class DecodeSession:
    """Sharding-aware owner of the model bundles + jitted decode entry
    points.

    ``policy`` fixes the session's DEFAULT decode policy (drafter ×
    acceptor × block schedule): every entry point is jitted once per
    (bundles, policy, geometry) — bundles are fixed at construction, so
    the per-session jit cache keys on (``DecodePolicy.cache_key``,
    geometry) — and the policy's loop-carried state is part of the
    sharded decode state (``sharding.policy.state_specs`` /
    ``slot_specs`` treat its batch-leading leaves like any other per-row
    array, with model-backed drafter caches spec'd under their own
    bundle's config).  ``serving_fns(policy=...)`` additionally builds
    per-policy serving functions for the engine's slot groups, sharing
    the same cache — one session serves heterogeneous per-request
    policies without recompiling.

    ``bundles`` ({name: core.bundle.ModelBundle}) are the session's
    auxiliary models — e.g. ``{"draft": ModelBundle(draft_params,
    draft_cfg)}`` for the ``draft_model`` policy.  Each bundle's params
    are device_put with its own ``param_shardings`` and threaded into
    every jitted entry point as an explicit argument, so they shard and
    cache-key exactly like the primary parameters; the static half of
    each bundle (cfg / kv_chunk / backend factory) is bound into the
    policy up front (``DecodePolicy.bind``), so incompatible bundles fail
    at construction, not at trace time.
    """

    def __init__(self, params, cfg: ModelConfig, dec: DecodeConfig, *,
                 mesh=None, kv_chunk: int = 0, backend=None,
                 jit: Optional[bool] = None, donate: Optional[bool] = None,
                 policy=None, bundles=None):
        self.cfg = cfg
        self.dec = dec
        self.bundles = dict(bundles or {})
        self.policy = policy_lib.resolve_policy(dec, policy).bind(
            self.bundles, cfg)
        self.mesh = mesh
        self.kv_chunk = kv_chunk
        self.backend = backend
        self.jit = (mesh is not None) if jit is None else bool(jit)
        self._donate = donate
        # a model-backed drafter exposes its bound model config as .cfg —
        # the sharding policy specs its loop-carried cache under it
        self.draft_cfg = getattr(self.policy.drafter, "cfg", None)
        if mesh is not None:
            self.param_shardings = sharding_policy.param_shardings(params, mesh)
            self.params = jax.device_put(params, self.param_shardings)
            self.aux_shardings = sharding_policy.bundle_param_shardings(
                self.bundles, mesh)
            self.aux_params = {n: jax.device_put(b.params,
                                                 self.aux_shardings[n])
                               for n, b in self.bundles.items()}
        else:
            self.param_shardings = None
            self.params = params
            self.aux_shardings = {}
            self.aux_params = {n: b.params for n, b in self.bundles.items()}
        self._fns: Dict[Any, Callable] = {}

    # -- placement helpers ---------------------------------------------------

    @property
    def donate(self) -> bool:
        """Donate loop-carried state buffers.  Defaults on for accelerator
        devices — XLA:CPU cannot alias donated buffers (it would only warn
        and copy), so host-mesh debug runs stay quiet.  Keyed off the
        session mesh's devices (the buffers live there), not the process
        default backend."""
        if self._donate is None:
            platform = (self.mesh.devices.flat[0].platform
                        if self.mesh is not None else jax.default_backend())
            self._donate = platform in ("gpu", "tpu")
        return self._donate

    def _with_mesh(self, fn):
        """Run (and, on first call, trace) ``fn`` under the session mesh so
        the model's internal GSPMD hints (``policy.maybe_shard``) activate."""
        if self.mesh is None:
            return fn
        mesh = self.mesh

        def call(*args):
            with jax.set_mesh(mesh):
                return fn(*args)

        call._cache_size = getattr(fn, "_cache_size", None)
        call._jitted = fn      # AOT access (launch/dryrun lowers these)
        return call

    def _constrain(self) -> Optional[Callable]:
        """State-constraint hook handed to the loop impls: pins the
        loop-carried NamedTuple state to its ``state_specs`` shardings."""
        if self.mesh is None:
            return None
        cfg, mesh = self.cfg, self.mesh

        draft_cfg = self.draft_cfg

        def constrain(state):
            specs = sharding_policy.state_specs(cfg, state, mesh,
                                                draft_cfg=draft_cfg)
            return jax.lax.with_sharding_constraint(
                state, sharding_policy.named(mesh, specs))

        return constrain

    def _out_shardings(self, fn, batch_size: int, *arg_structs):
        """Explicit output shardings: batch-leading arrays over the data
        axes, scalars/aggregates replicated."""
        mesh = self.mesh
        ax = sharding_policy.batch_axes(mesh, batch_size)

        def rule(s):
            if s.ndim >= 1 and s.shape[0] == batch_size:
                return NamedSharding(mesh, P(*([ax] + [None] * (s.ndim - 1))))
            return NamedSharding(mesh, P())

        return jax.tree_util.tree_map(rule, jax.eval_shape(fn, *arg_structs))

    def _get(self, key, build):
        fn = self._fns.get(key)
        if fn is None:
            fn = build()
            self._fns[key] = fn
        return fn

    def _jit_entry(self, fn, batch: Dict, extra_in=(), extra_structs=()):
        """jit one run-to-completion entry point with explicit shardings.

        Every entry point takes ``(params, aux, batch, *extra)`` — ``aux``
        is the {bundle name: params} dict of auxiliary models, sharded per
        bundle (empty dict for single-model sessions)."""
        if self.mesh is None:
            return jax.jit(fn)
        mesh = self.mesh
        b = next(iter(batch.values())).shape[0]
        in_sh = (self.param_shardings, self.aux_shardings,
                 sharding_policy.named(
                     mesh, sharding_policy.batch_specs(mesh, batch)),
                 *extra_in)
        out_sh = self._out_shardings(fn, b, _structs(self.params),
                                     _structs(self.aux_params),
                                     _structs(batch), *extra_structs)
        return self._with_mesh(
            jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh))

    # -- run-to-completion entry points -------------------------------------

    def decode(self, batch: Dict, *, max_new_rows=None):
        """Blockwise parallel decode (causal LM).  See core.decode.bpd_decode."""
        cfg, dec, pol = self.cfg, self.dec, self.policy
        if not self.jit:
            return decode_lib._bpd_decode_impl(
                self.params, cfg, dec, batch, max_new_rows,
                backend=self.backend, kv_chunk=self.kv_chunk, policy=pol,
                aux_params=self.aux_params)

        b = batch["tokens"].shape[0]
        budget = (jnp.full((b,), dec.max_new_tokens, I32)
                  if max_new_rows is None else jnp.asarray(max_new_rows, I32))

        def build():
            backend, kv_chunk = self.backend, self.kv_chunk
            constrain = self._constrain()

            def fn(params, aux, batch, budget):
                return decode_lib._bpd_decode_impl(
                    params, cfg, dec, batch, budget, backend=backend,
                    kv_chunk=kv_chunk, constrain=constrain, policy=pol,
                    aux_params=aux)

            extra_in, extra_structs = (), (jax.ShapeDtypeStruct((b,), I32),)
            if self.mesh is not None:
                ax = sharding_policy.batch_axes(self.mesh, b)
                extra_in = (NamedSharding(self.mesh, P(ax)),)
            return self._jit_entry(fn, batch, extra_in, extra_structs)

        fn = self._get(("bpd", pol.cache_key) + _geometry(batch), build)
        return fn(self.params, self.aux_params, batch, budget)

    def greedy(self, batch: Dict):
        """Greedy baseline (p_1 only).  See core.decode.greedy_decode."""
        cfg, dec = self.cfg, self.dec
        if not self.jit:
            return decode_lib._greedy_decode_impl(
                self.params, cfg, dec, batch, kv_chunk=self.kv_chunk)

        def build():
            kv_chunk = self.kv_chunk
            constrain = self._constrain()

            def fn(params, aux, batch):
                del aux  # greedy never drafts — uniform signature only
                return decode_lib._greedy_decode_impl(
                    params, cfg, dec, batch, kv_chunk=kv_chunk,
                    constrain=constrain)

            return self._jit_entry(fn, batch)

        fn = self._get(("greedy",) + _geometry(batch), build)
        return fn(self.params, self.aux_params, batch)

    def decode_seq2seq(self, batch: Dict):
        """Encode once, BPD the decoder.  See core.decode.bpd_decode_seq2seq."""
        cfg, dec, pol = self.cfg, self.dec, self.policy
        if not self.jit:
            return decode_lib._bpd_decode_seq2seq_impl(
                self.params, cfg, dec, batch, policy=pol,
                aux_params=self.aux_params)

        def build():
            constrain = self._constrain()

            def fn(params, aux, batch):
                return decode_lib._bpd_decode_seq2seq_impl(
                    params, cfg, dec, batch, constrain=constrain, policy=pol,
                    aux_params=aux)

            return self._jit_entry(fn, batch)

        fn = self._get(("s2s", pol.cache_key) + _geometry(batch), build)
        return fn(self.params, self.aux_params, batch)

    # -- serving (continuous batching) ---------------------------------------

    def bound_policy(self, policy=None):
        """Resolve ``policy`` (a registered name / DecodePolicy / None for
        the session default) and bind the session's bundles to it — the
        form every serving slot group runs."""
        if policy is None:
            return self.policy
        return policy_lib.resolve_policy(self.dec, policy).bind(
            self.bundles, self.cfg)

    def serving_fns(self, ecfg: EngineConfig, *, policy=None) -> ServingFns:
        """Compile-once device functions for the continuous-batching engine.

        All four are geometry-fixed by ``ecfg``: prompts are padded to
        ``max_prompt_len`` and slot indices are traced int32 scalars, so
        admit/step/evict each compile exactly once regardless of traffic —
        on a single device and on a ``("data", "model")`` mesh alike.

        ``policy`` overrides the session default for one policy slot group
        (per-request decode policies): the returned functions are built for
        that policy and CACHED per (policy identity, geometry) — the jit
        cache keys on ``DecodePolicy.cache_key``, so two groups running the
        same policy at the same geometry share one compiled step, and a
        heterogeneous engine compiles exactly one step per distinct
        (policy, geometry) with no per-step recompilation.
        """
        pol = self.bound_policy(policy)
        key = ("serving", pol.cache_key, ecfg)
        return self._get(key, lambda: self._build_serving_fns(ecfg, pol))

    def _build_serving_fns(self, ecfg: EngineConfig,
                           pol) -> ServingFns:
        cfg, dec, mesh = self.cfg, self.dec, self.mesh
        block_k = dec.block_k or cfg.bpd_k
        prefix = cfg.num_meta_tokens
        context_len = prefix + ecfg.max_prompt_len + ecfg.max_new_cap
        buf_len = ecfg.max_prompt_len + ecfg.max_new_cap + block_k
        backend = self.backend or decode_lib.causal_lm_backend(
            cfg, kv_chunk=self.kv_chunk)
        s = ecfg.num_slots

        # KV-cache backend (dense slab vs managed page pool).  One host
        # allocator per slot group drives the mapping for every layer, so
        # the block-table geometry is computed here once.
        paged_geom = None
        if dec.cache_backend == "paged":
            ps = dec.page_size
            P_ = cache_lib.pages_per_row(context_len, block_k, ps)
            pool = ecfg.page_pool_pages or (1 + s * P_)
            kv_backend: cache_lib.KVCacheBackend = cache_lib.PagedBackend(
                ps, num_pages=pool, managed=True)
            paged_geom = PagedGeometry(page_size=ps, pages_per_row=P_,
                                       num_pages=pool, prefix_len=prefix)
        else:
            kv_backend = cache_lib.get_backend(dec)

        def slots_batch(n: int) -> Dict:
            """Pseudo decode-entry batch for policy-state builders: the
            engine admits padded prompts, so drafters see a zeroed
            ``tokens`` batch of the admission geometry — this keeps their
            state SHAPES identical across init (n = num_slots, no params),
            admit (n = 1, prefilled for real) and evict (reset rows).
            ``src`` (same padded geometry) lets source-drafting policies
            (``input_copy``) serve through the engine: admission scatters
            the request's real source row over these zeros."""
            z = jnp.zeros((n, ecfg.max_prompt_len), I32)
            return {"tokens": z, "src": z}

        def init_slots(gid) -> SlotBatch:
            zeros = lambda: jnp.zeros((s,), I32)  # noqa: E731
            return SlotBatch(
                tokens=jnp.zeros((s, buf_len), I32),
                text_len=zeros(),
                prompt_len=zeros(),
                proposals=jnp.zeros((s, block_k), I32),
                caches=model_lib.init_caches(cfg, s, context_len, block_k,
                                             backend=kv_backend),
                active=jnp.zeros((s,), bool),
                finished=jnp.ones((s,), bool),  # empty slots read as finished
                generated=zeros(),
                max_new=zeros(),
                invocations=zeros(),
                policy_state=pol.init_state(cfg, dec, slots_batch(s), s),
                group=jnp.full((s,), gid, I32),
            )

        slot_sh = cache_sh = None
        if mesh is not None:
            struct = jax.eval_shape(init_slots, jax.ShapeDtypeStruct((), I32))
            slot_sh = sharding_policy.named(
                mesh, sharding_policy.slot_specs(cfg, struct, mesh,
                                                 policy=pol))
            cache_sh = slot_sh.caches

        def prefill(params, aux, prompts, plens, srcs) -> PrefillPacket:
            """The slot-free half of admission: prefill ``W`` padded
            prompts in ONE forward and return their handoff packet.

            Per-row computation is identical to the historical batch-1
            admission prefill (rows never mix — embeddings, attention and
            the per-row policy init are all row-local), so a packet row
            attached later is bit-for-bit the state ``admit`` would have
            scattered directly.  Batching amortizes the per-dispatch host
            overhead across ``W`` prompts — the disaggregated engine's
            main throughput lever — and gives prefill its own wide-
            sequence compute shape, distinct from the decode step's
            memory-bound block-verify geometry.

            Per-slot policy state is built fresh here — a packet row never
            inherits a previous occupant's drafter/schedule state — and
            the policy's drafter proposes the first block (a model-backed
            drafter prefills its own cache on the padded prompts, with its
            params from ``aux``; a source-drafting policy stores the
            request's src rows).
            """
            w = prompts.shape[0]
            row_caches = kv_backend.row_init(cfg, context_len, block_k,
                                             batch=w)
            h = model_lib.embed_inputs(params, cfg, {"tokens": prompts})
            positions = jnp.arange(h.shape[1], dtype=I32)
            hidden, _, row_caches = model_lib.forward_hidden(
                params, cfg, h, positions=positions, caches=row_caches,
                moe_full_capacity=True)
            idx = (prefix + plens - 1)[:, None, None]
            last = jnp.take_along_axis(
                hidden, jnp.broadcast_to(idx, (w, 1, hidden.shape[2])),
                axis=1)[:, 0]
            logits = model_lib.all_head_logits(params, cfg, last)  # (W, K, V)

            row_ps = pol.init_state(cfg, dec,
                                    {"tokens": prompts, "src": srcs}, w,
                                    aux=aux)
            last_tok = jnp.take_along_axis(
                prompts, jnp.maximum(plens - 1, 0)[:, None], axis=1)[:, 0]
            proposals, row_ds = decode_lib.initial_draft(
                pol, logits, plens, block_k, row_ps.drafter,
                prev_token=last_tok, aux_params=aux)
            row_ps = row_ps._replace(drafter=row_ds)

            tokens = jnp.zeros((w, buf_len), I32)
            tokens = tokens.at[:, :ecfg.max_prompt_len].set(prompts)
            return PrefillPacket(tokens=tokens,
                                 prompt_len=jnp.asarray(plens, I32),
                                 proposals=proposals, caches=row_caches,
                                 policy_state=row_ps)

        def attach(state: SlotBatch, packet: PrefillPacket, row, slot,
                   max_new, tbl_row=None, write_mask=None) -> SlotBatch:
            """The scatter-only half of admission: install packet ``row``
            into slot ``slot`` — the prefill→decode KV handoff.

            The packet row is replicated work (its slice never splits the
            data axis); the writes into the slot batch are a global scatter
            constrained back to the slot shardings, so only the data shard
            owning ``slot`` mutates its rows.  Under a pod mesh the packet
            rows live on the ``pod`` axis and the scatter reshards them
            into the ("pod", "data")-split slot slab — the measured
            device-to-device handoff transfer (launch/dryrun.py).

            Under the paged backend the packet rows are dense page-aligned
            workspaces (``PagedBackend.row_init``); ``tbl_row`` ((P,)
            int32) and ``write_mask`` ((P,) bool) are the host allocator's
            physical mapping for this slot — copy-on-write prefix hits
            arrive with ``write_mask=False`` and are left untouched in the
            pool.
            """
            take = lambda x: jax.lax.dynamic_slice_in_dim(  # noqa: E731
                x, row, 1, axis=0)
            row_caches = jax.tree_util.tree_map(take, packet.caches)
            row_ps = jax.tree_util.tree_map(take, packet.policy_state)
            prompt_len = take(packet.prompt_len)[0]
            upd = lambda arr, val: arr.at[slot].set(val)  # noqa: E731
            policy_state = jax.tree_util.tree_map(
                lambda full, r: full.at[slot].set(r[0]),
                state.policy_state, row_ps)
            return state._replace(
                tokens=upd(state.tokens, take(packet.tokens)[0]),
                text_len=upd(state.text_len, prompt_len),
                prompt_len=upd(state.prompt_len, prompt_len),
                proposals=upd(state.proposals, take(packet.proposals)[0]),
                caches=model_lib.scatter_cache_row(state.caches, row_caches,
                                                   slot, constraint=cache_sh,
                                                   tbl_row=tbl_row,
                                                   write_mask=write_mask),
                active=upd(state.active, True),
                finished=upd(state.finished, False),
                generated=upd(state.generated, 0),
                max_new=upd(state.max_new, max_new),
                invocations=upd(state.invocations, 1),  # the prefill call
                policy_state=policy_state,
            )

        def attach_many(state: SlotBatch, packet: PrefillPacket, rows, slots,
                        max_news, valid, tbl_rows=None,
                        write_masks=None) -> SlotBatch:
            """Batched KV handoff: install up to W packet rows into W freed
            slots in ONE dispatch.  A per-request attach call would hand
            back the admission dispatch overhead that batching the prefill
            just amortized — this keeps the whole admission path at O(1)
            dispatches per worker batch.  ``valid`` masks the short final
            batch: invalid lanes are skipped entirely (``lax.cond``), so
            padding writes nothing and the call compiles once at width W.
            """
            w_ = rows.shape[0]
            for i in range(w_):
                extra = (() if tbl_rows is None
                         else (tbl_rows[i], write_masks[i]))

                def _install(st, i=i, extra=extra):
                    return attach(st, packet, rows[i], slots[i],
                                  max_news[i], *extra)

                state = jax.lax.cond(valid[i], _install, lambda st: st,
                                     state)
            return state

        def admit(params, aux, state: SlotBatch, slot, prompt, prompt_len,
                  max_new, src, tbl_row=None, write_mask=None) -> SlotBatch:
            """Unified admission = ``attach ∘ prefill`` at width 1: prefill
            one padded prompt and scatter it into row ``slot`` in a single
            jitted call.  Composing the two halves (instead of duplicating
            their bodies) is what makes the disaggregated engine token-
            identical to this path by construction."""
            packet = prefill(params, aux, prompt[None],
                             jnp.asarray(prompt_len, I32)[None], src[None])
            return attach(state, packet, jnp.zeros((), I32), slot, max_new,
                          tbl_row, write_mask)

        def step(params, aux, state: SlotBatch):
            bst = decode_lib.BPDState(
                tokens=state.tokens, text_len=state.text_len,
                proposals=state.proposals, caches=state.caches,
                finished=state.finished, iters=jnp.zeros((), I32),
                generated=state.generated, policy_state=state.policy_state)
            out = decode_lib.bpd_iteration(
                params, cfg, dec, backend, bst, prefix_offset=prefix,
                max_new=state.max_new, active=state.active, policy=pol,
                aux_params=aux)
            stepped = state.active & ~state.finished
            new_state = state._replace(
                tokens=out.tokens, text_len=out.text_len,
                proposals=out.proposals, caches=out.caches,
                finished=out.finished, generated=out.generated,
                invocations=state.invocations + stepped.astype(I32),
                policy_state=out.policy_state)
            # fused harvest decision: one tiny (S,) array carries both the
            # active and the finished bits, so the host loop round-trips a
            # single transfer per step (bit 0 = active, bit 1 = harvestable)
            status = (state.active.astype(jnp.int8)
                      + 2 * (state.active & out.finished).astype(jnp.int8))
            return new_state, status

        k_win = max(int(getattr(ecfg, "steps_per_sync", 1)), 1)

        def step_windowed(params, aux, state: SlotBatch):
            """Up to ``steps_per_sync`` decode iterations fused into ONE
            dispatch — a bounded while_loop over the SAME traced step
            body, so the commit stream is bitwise identical to stepping
            one iteration at a time.  The loop exits the moment any row
            becomes harvestable: finished slots surface to the host at
            the same iteration they would have with per-step syncs, so
            slot refill (the continuous-batching win) keeps its timing;
            only the admission of NEW arrivals can lag by at most
            ``steps_per_sync - 1`` iterations.  Returns the number of
            iterations actually run so the engine's model-invocation
            accounting stays honest (a window is 1..k dispatched
            forwards, not one)."""
            if k_win == 1:
                nst, status = step(params, aux, state)
                return nst, status, jnp.ones((), I32)

            def body(carry):
                st, _, i = carry
                nst, status = step(params, aux, st)
                return nst, status, i + 1

            def cond(carry):
                _, status, i = carry
                return (i < k_win) & ~jnp.any((status & 2) > 0)

            st, status, iters = jax.lax.while_loop(
                cond, body,
                (state, jnp.zeros((s,), jnp.int8), jnp.zeros((), I32)))
            return st, status, iters

        def evict(state: SlotBatch, mask) -> SlotBatch:
            # evicted slots also drop their policy state, so a paused slot
            # can never leak schedule/drafter history into a later request
            # (paramless init: model-backed drafters reset to empty caches
            # of the same admission geometry — admit rebuilds them anyway)
            fresh = pol.init_state(cfg, dec, slots_batch(s), s)
            policy_state = jax.tree_util.tree_map(
                lambda full, init: jnp.where(
                    mask.reshape((-1,) + (1,) * (init.ndim - 1)), init, full),
                state.policy_state, fresh)
            return state._replace(
                active=state.active & ~mask,
                caches=model_lib.reset_cache_rows(state.caches, mask),
                policy_state=policy_state)

        # the slot state is donated on accelerators, so a step or an
        # admission never holds two copies of the KV slab
        state_dn = (2,) if self.donate else ()  # state follows (params, aux)
        first_dn = (0,) if self.donate else ()
        if mesh is None:
            return ServingFns(init=jax.jit(init_slots),
                              admit=jax.jit(admit, donate_argnums=state_dn),
                              step=jax.jit(step_windowed,
                                           donate_argnums=state_dn),
                              evict=jax.jit(evict, donate_argnums=first_dn),
                              prefill=jax.jit(prefill),
                              attach=jax.jit(attach, donate_argnums=first_dn),
                              attach_many=jax.jit(attach_many,
                                                  donate_argnums=first_dn),
                              paged=paged_geom)

        rep = NamedSharding(mesh, P())
        mask_sh = NamedSharding(mesh, P(sharding_policy.batch_axes(mesh, s)))
        aux_sh = self.aux_shardings
        admit_in = (self.param_shardings, aux_sh, slot_sh, rep,
                    rep, rep, rep, rep)
        if paged_geom is not None:
            admit_in = admit_in + (rep, rep)  # tbl_row, write_mask
        # prefill-worker geometry: packet rows shard over the pod axis
        # (prefill workers own their data-axis slice); the attach scatter
        # reshards them into the ("pod", "data")-split slot slab — the
        # sharding-constrained prefill→decode handoff transfer
        w = max(ecfg.prefill_slots, 1)
        pkt_struct = jax.eval_shape(
            prefill, _structs(self.params), _structs(self.aux_params),
            jax.ShapeDtypeStruct((w, ecfg.max_prompt_len), I32),
            jax.ShapeDtypeStruct((w,), I32),
            jax.ShapeDtypeStruct((w, ecfg.max_prompt_len), I32))
        pkt_sh = sharding_policy.named(
            mesh, sharding_policy.packet_specs(cfg, pkt_struct, mesh,
                                               policy=pol))
        pre_ax = sharding_policy.prefill_axes(mesh, w)
        prompts_sh = NamedSharding(mesh, P(pre_ax, None))
        plens_sh = NamedSharding(mesh, P(pre_ax))
        attach_in = (slot_sh, pkt_sh, rep, rep, rep)
        attach_many_in = (slot_sh, pkt_sh, rep, rep, rep, rep)
        if paged_geom is not None:
            attach_in = attach_in + (rep, rep)  # tbl_row, write_mask
            attach_many_in = attach_many_in + (rep, rep)
        return ServingFns(
            init=self._with_mesh(jax.jit(init_slots, in_shardings=(rep,),
                                         out_shardings=slot_sh)),
            admit=self._with_mesh(jax.jit(
                admit,
                in_shardings=admit_in,
                out_shardings=slot_sh, donate_argnums=state_dn)),
            step=self._with_mesh(jax.jit(
                step_windowed,
                in_shardings=(self.param_shardings, aux_sh, slot_sh),
                out_shardings=(slot_sh, rep, rep),
                donate_argnums=state_dn)),
            evict=self._with_mesh(jax.jit(
                evict, in_shardings=(slot_sh, mask_sh),
                out_shardings=slot_sh, donate_argnums=first_dn)),
            prefill=self._with_mesh(jax.jit(
                prefill,
                in_shardings=(self.param_shardings, aux_sh, prompts_sh,
                              plens_sh, prompts_sh),
                out_shardings=pkt_sh)),
            attach=self._with_mesh(jax.jit(
                attach, in_shardings=attach_in, out_shardings=slot_sh,
                donate_argnums=first_dn)),
            attach_many=self._with_mesh(jax.jit(
                attach_many, in_shardings=attach_many_in,
                out_shardings=slot_sh, donate_argnums=first_dn)),
            paged=paged_geom,
        )
