#!/usr/bin/env python3
"""Serve granite-3-8b at its published widths on TPU, and check the result.

    python3 chip_smoke.py               # one chip: 16 of the 40 layers
    python3 chip_smoke.py --chips 4     # four chips: the sharded path only

One chip (TPU v5e, 16 GB) holds 16 of granite-3-8b's 40 layers at the
published widths — d_model 4096, 32 query and 8 KV heads of 128, d_ff
12800, vocab 49155 padded to 49408, 8 BPD heads — in bf16: about 4.2 B
parameters, 8.5 GB.  A dense model's period is one layer, so the cut
layers stand for further pipeline stages.  The weights are random, drawn
from ``--seed``: the model writes degenerate, repetitive text, so the
acceptance k̂ it reports says nothing about a trained model's.  The run
shows that the serving path works at real widths, not what blockwise
decoding is worth.

Default phases, each through the library's own entry points:

  1. parameters drawn in one jitted program, straight into bf16;
  2. 32 requests through ``Scheduler`` + ``ContinuousBatchingEngine``
     (16 slots, ``exact`` policy, dense KV cache): every request gets its
     full budget, every device function compiles once, and the p1 logits
     over every served sequence are finite;
  3. one request streamed over HTTP/SSE on localhost
     (``HTTPServer`` → ``Frontend`` → ``Scheduler``);
  4. p1 logits of prefill-then-cached-verify against the model's own
     uncached forward, and exact BPD against ``greedy_decode``;
  5. the fused verify kernel, compiled, against the unfused acceptors.

``--chips 4`` runs only the sharded path: the 16-layer cut on one device
against the same cut on a ("data", "model") = (1, 4) mesh, then the whole
40-layer model initialized straight into its shardings and served through
the engine on that mesh.

Exits non-zero, printing no result line, when JAX finds no TPU or any
check fails.  The last line of a passing run is one JSON object naming
the device.
"""
from __future__ import annotations

import argparse
import asyncio
import functools
import json
import os
import sys
import time
from typing import NamedTuple

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec  # noqa: E402

from repro.config import DecodeConfig, get_config  # noqa: E402
from repro.core import decode as decode_lib  # noqa: E402
from repro.core import policy as policy_lib  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import stream_one  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.serving import (ContinuousBatchingEngine, DecodeSession,  # noqa: E402
                           EngineConfig, Frontend, HTTPServer, Request,
                           Scheduler, tracing)

ARCH = "granite-3-8b"
ONE_CHIP_LAYERS = 16

# Two bf16 paths through the same weights (cached vs uncached forward,
# padded vs exact-length prefill, 8-wide verify vs 1-token steps) round
# their activations differently at each of the layers.  bf16 keeps 8
# significand bits, so one rounding moves a logit near the top of the
# random model's range (|logit| ≲ 8) by up to 2^-5; a few such roundings
# reach the logits through the residual stream and the tied unembedding.
# 0.25 is 8 of those roundings: a path that disagrees by more computes
# something else, not the same sum in another order.
LOGIT_TOL = 0.25


class Failed(RuntimeError):
    """A check of the smoke run failed."""


def check(ok, msg):
    if not ok:
        raise Failed(msg)


def log(msg):
    print(f"[chip_smoke] {msg}", flush=True)


# ---------------------------------------------------------------------------
# configuration and workload
# ---------------------------------------------------------------------------


def published_config(num_layers: int):
    """granite-3-8b at its published widths, bf16 weights, ``num_layers``
    of the 40 layers."""
    return get_config(ARCH).replace(num_layers=num_layers,
                                    param_dtype="bfloat16")


def make_requests(cfg, n, prompt_range, new_range, seed):
    """``n`` requests with uniform random prompt tokens; prompt lengths and
    budgets drawn uniformly from the inclusive ranges."""
    rng = np.random.default_rng(seed)
    out = []
    for rid in range(n):
        plen = int(rng.integers(prompt_range[0], prompt_range[1] + 1))
        out.append(Request(
            rid=rid,
            prompt=rng.integers(0, cfg.vocab_size, plen).astype(np.int32),
            max_new=int(rng.integers(new_range[0], new_range[1] + 1))))
    return out


def engine_for(params, cfg, *, num_slots, max_prompt_len, max_new_cap,
               mesh=None):
    dec = DecodeConfig(max_new_tokens=max_new_cap, block_k=cfg.bpd_k,
                       policy="exact")
    ecfg = EngineConfig(num_slots=num_slots, max_prompt_len=max_prompt_len,
                        max_new_cap=max_new_cap)
    return ContinuousBatchingEngine(params, cfg, dec, ecfg, mesh=mesh)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def serve_requests(engine, requests):
    """Serve ``requests`` to completion; every one must get its full budget
    and every engine device function must have compiled exactly once.
    Returns ({rid: FinishedRequest}, wall seconds)."""
    sched = Scheduler(engine)
    for r in requests:
        sched.submit(r)
    t0 = time.monotonic()
    finished = sched.run()
    wall = time.monotonic() - t0
    done = {f.rid: f for f in finished}
    check(sorted(done) == sorted(r.rid for r in requests),
          f"served {sorted(done)}, submitted "
          f"{sorted(r.rid for r in requests)}")
    for r in requests:
        f = done[r.rid]
        check(f.generated == r.max_new and len(f.tokens) == r.max_new,
              f"request {r.rid}: {f.generated} tokens "
              f"({len(f.tokens)} returned), budget {r.max_new}")
    counts = engine.compile_counts()
    check(counts and all(n == 1 for n in counts.values()),
          f"engine compile counts {counts}: want one per device function")
    return done, wall


def uncached_p1(cfg):
    """The model's own uncached forward: p1 logits at every position."""

    @jax.jit
    def uncached_forward(params, tokens):
        h = M.embed_inputs(params, cfg, {"tokens": tokens})
        positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
        hidden, _, _ = M.forward_hidden(params, cfg, h, positions=positions)
        return M.base_logits(params, cfg, hidden)

    return uncached_forward


def padded_row(tokens, length):
    row = np.zeros((1, length), np.int32)
    row[0, :len(tokens)] = tokens
    return jnp.asarray(row)


def check_finite(fwd, params, requests, done, length):
    """p1 logits of the uncached forward ``fwd`` over every served sequence
    (prompt + output, padded to ``length``; causal, so the padding never
    reaches a real position) are finite.  Returns positions checked."""
    checked = 0
    for r in requests:
        seq = np.concatenate([r.prompt, done[r.rid].tokens])
        finite = jnp.all(jnp.isfinite(fwd(params, padded_row(seq, length))),
                         axis=-1)
        n = len(seq)
        check(bool(np.asarray(finite)[0, :n].all()),
              f"request {r.rid}: non-finite p1 logits")
        checked += n
    return checked


def cached_verify(engine, params, requests):
    """Prefill-then-cached-verify, through the engine's own functions: the
    session's padded prefill builds each prompt's KV cache and first-block
    drafts, then one k-wide verify forward runs against that cache.
    Returns (p1 logits (W, k, V) f32, drafts (W, k))."""
    sess = engine.session
    fns = sess.serving_fns(engine.ecfg)
    cfg, width = engine.cfg, engine.ecfg.max_prompt_len
    prompts = np.zeros((len(requests), width), np.int32)
    plens = np.zeros((len(requests),), np.int32)
    for i, r in enumerate(requests):
        prompts[i, :len(r.prompt)] = r.prompt
        plens[i] = len(r.prompt)
    prompts, plens = jnp.asarray(prompts), jnp.asarray(plens)
    packet = fns.prefill(sess.params, sess.aux_params, prompts, plens, prompts)
    backend = decode_lib.causal_lm_backend(cfg)

    @jax.jit
    def verify_forward(params, drafts, caches, length):
        h = backend.embed_tokens(params, drafts)
        hidden, _ = backend.decode_block(params, h, caches, length)
        return backend.p1_logits(params, hidden)             # (W, k, V)

    logits = verify_forward(sess.params, packet.proposals, packet.caches,
                            plens + cfg.num_meta_tokens)
    check(bool(jnp.all(jnp.isfinite(logits))),
          "non-finite p1 logits in the verify step")
    return logits.astype(jnp.float32), packet.proposals


def compare_cached(fwd, params, cfg, requests, p1_cached, drafts, length):
    """Cached-verify p1 logits against the uncached forward ``fwd`` of the
    same tokens.  Returns the largest absolute difference."""
    k = drafts.shape[1]
    worst = 0.0
    for i, r in enumerate(requests):
        p = len(r.prompt)
        seq = np.concatenate([r.prompt, np.asarray(drafts[i])])
        ref = fwd(params, padded_row(seq, length))[0, p:p + k]
        diff = jnp.abs(ref.astype(jnp.float32) - p1_cached[i])
        worst = max(worst, float(jnp.max(diff[:, :cfg.vocab_size])))
    check(worst <= LOGIT_TOL,
          f"cached vs uncached p1 logits differ by {worst} > {LOGIT_TOL}")
    return worst


def compare_greedy(fwd, params, cfg, requests, done, length):
    """Exact BPD (the engine) against ``greedy_decode`` on the same prompts.
    Returns (share of equal tokens, [(rid, first divergence, top-2 margin
    there)]).  A divergence is only admitted where the uncached forward's
    top-2 margin is below LOGIT_TOL: a near-tie that bf16 rounding may
    flip."""
    equal = total = 0
    divergences = []
    for r in requests:
        dec = DecodeConfig(max_new_tokens=r.max_new, block_k=1)
        toks, _ = decode_lib.greedy_decode(
            params, cfg, dec, {"tokens": jnp.asarray(r.prompt)[None]},
            session=DecodeSession(params, cfg, dec, jit=True))
        p = len(r.prompt)
        greedy = np.asarray(toks)[0, p:p + r.max_new]
        bpd = done[r.rid].tokens
        same = greedy == bpd
        equal += int(same.sum())
        total += len(bpd)
        if not same.all():
            j = int(np.argmin(same))
            seq = np.concatenate([r.prompt, bpd[:j]])
            row = np.asarray(fwd(params, padded_row(seq, length))[0, p + j - 1],
                             np.float32)[:cfg.vocab_size]
            top2 = np.sort(row)[-2:]
            margin = float(top2[1] - top2[0])
            divergences.append((r.rid, j, margin))
            check(margin < LOGIT_TOL,
                  f"request {r.rid}: BPD and greedy first differ at token "
                  f"{j}, where the top-2 margin {margin} is not a near-tie")
    return equal / total, divergences


def check_fused_verify(p1, drafts, *, compiled=True):
    """The fused verify kernel on the p1 logits of a real verify step, on
    three kinds of drafts: the heads' own, the verifier's greedy chain
    (all accepted) and that chain broken halfway.  Its accepts, k̂ and
    tokens must equal the unfused ``ExactAcceptor`` and
    ``TopKAcceptor(top_k=2)``.  ``compiled`` requires a Mosaic kernel in
    the compiled program (no interpreter).  Returns the k̂ rows."""
    w, k, vocab = p1.shape
    greedy = jnp.argmax(p1, axis=-1).astype(jnp.int32)
    chain = jnp.concatenate([drafts[:, :1], greedy[:, :k - 1]], axis=1)
    broken = chain.at[:, k // 2].set((chain[:, k // 2] + 1) % vocab)
    props = jnp.concatenate([drafts.astype(jnp.int32), chain, broken])
    logits = jnp.concatenate([p1, p1, p1])
    slot = jnp.arange(k)[None, :]
    khats = {}
    for crit, acceptor in (("exact", policy_lib.ExactAcceptor()),
                           ("topk", policy_lib.TopKAcceptor(top_k=2))):
        fn = jax.jit(functools.partial(
            ops.fused_verify, criterion=crit, top_k=2,
            interpret=not compiled))
        exe = fn.lower(logits, props).compile()
        if compiled:
            check("tpu_custom_call" in exe.as_text(),
                  f"fused verify ({crit}) compiled without its kernel")
        acc, khat, toks, nxt = exe(logits, props)
        want = acceptor.accepts(props, logits)
        want_khat = jnp.where(jnp.all(want, axis=1), k,
                              jnp.argmin(want, axis=1)).astype(jnp.int32)
        want_toks = jnp.where(slot < want_khat[:, None], props, 0)
        want_nxt = jnp.take_along_axis(
            jnp.argmax(logits, axis=-1), (want_khat - 1)[:, None], axis=1)[:, 0]
        for name, got, exp in (("accepts", acc, want), ("k̂", khat, want_khat),
                               ("tokens", toks, want_toks),
                               ("next", nxt, want_nxt)):
            check(np.array_equal(np.asarray(got), np.asarray(exp)),
                  f"fused verify ({crit}) {name} {np.asarray(got).tolist()} "
                  f"!= unfused {np.asarray(exp).tolist()}")
        khats[crit] = np.asarray(khat).tolist()
    check(all(kh == k for kh in khats["exact"][w:2 * w]),
          "the greedy chain was not accepted whole")
    return khats


def serve_http(engine, prompt, max_new):
    """Stream one request over HTTP/SSE on localhost through a fresh
    scheduler on the (already compiled) engine."""

    async def run():
        srv = HTTPServer(Frontend(Scheduler(engine)), host="127.0.0.1",
                         port=0)
        await srv.start()
        try:
            return await stream_one(srv, prompt, max_new)
        finally:
            await srv.stop()

    _, done = asyncio.run(run())
    check(done["generated"] == max_new and len(done["tokens"]) == max_new,
          f"HTTP request got {done['generated']} tokens, budget {max_new}")
    return done


def prefill_p1(cfg, in_shardings=None):
    """Prefill forward (caches written, as admission does) returning p1
    logits at every prompt position; ``in_shardings`` (parameters, tokens)
    places it on a mesh."""

    def prefill_forward(params, tokens):
        b, s = tokens.shape
        caches = M.init_caches(cfg, b, s, 1)
        h = M.embed_inputs(params, cfg, {"tokens": tokens})
        hidden, _, _ = M.forward_hidden(
            params, cfg, h, positions=jnp.arange(s, dtype=jnp.int32),
            caches=caches)
        return M.base_logits(params, cfg, hidden).astype(jnp.float32)

    if in_shardings is None:
        return jax.jit(prefill_forward)
    return jax.jit(prefill_forward, in_shardings=in_shardings)


def compare_sharded(cfg, mesh, tokens, seed):
    """The same parameters alone on one device and spread over ``mesh``:
    their prefill p1 logits agree within LOGIT_TOL.  Returns the largest
    absolute difference."""
    params = M.init_params(jax.random.PRNGKey(seed), cfg)
    single = np.asarray(prefill_p1(cfg)(params, tokens))
    sess = DecodeSession(params, cfg, DecodeConfig(), mesh=mesh)
    del params
    replicated = NamedSharding(mesh, PartitionSpec())
    with jax.set_mesh(mesh):
        spread = np.asarray(prefill_p1(
            cfg, (sess.param_shardings, replicated))(sess.params, tokens))
    worst = float(np.abs(single - spread)[..., :cfg.vocab_size].max())
    check(worst <= LOGIT_TOL,
          f"1-device vs {dict(mesh.shape)} mesh p1 logits differ by {worst} "
          f"> {LOGIT_TOL}")
    return worst


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def log_compiles(before):
    """Backend compiles since the ``tracing.snapshot()`` ``before``, from
    the program's own compile counter."""
    now = tracing.snapshot()
    n = now["compiles_total"] - before["compiles_total"]
    sec = now["compile_seconds_total"] - before["compile_seconds_total"]
    log(f"compiled {n} programs in {sec:.2f} s")


def log_memory(after, devices):
    """Device memory in use and its peak so far, per device."""
    for d in devices:
        stats = d.memory_stats() or {}
        log(f"after {after}: device {d.id} bytes_in_use "
            f"{stats.get('bytes_in_use')}, peak_bytes_in_use "
            f"{stats.get('peak_bytes_in_use')}")


class Sizes(NamedTuple):
    slots: int          # engine slots
    requests: int       # requests served through the engine
    prompt: tuple       # inclusive range of prompt lengths
    new: tuple          # inclusive range of generation budgets


ONE_CHIP = Sizes(slots=16, requests=32, prompt=(256, 1024), new=(64, 256))
FOUR_CHIPS = Sizes(slots=16, requests=16, prompt=(128, 512), new=(32, 128))


def count_params(params):
    return sum(x.size for x in jax.tree_util.tree_leaves(params))


def one_chip(cfg, seed, sizes=ONE_CHIP, *, compiled=True):
    """The default phases on one device; ``compiled=False`` runs the fused
    verify kernel in interpret mode (the CPU rehearsal)."""
    t0 = time.monotonic()
    params = M.init_params(jax.random.PRNGKey(seed), cfg)
    jax.block_until_ready(params)
    log(f"{cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"vocab {cfg.vocab_size} (padded {cfg.padded_vocab_size}), "
        f"k={cfg.bpd_k}: {count_params(params):,} {cfg.param_dtype} "
        f"parameters in {time.monotonic() - t0:.1f} s")
    devices = [jax.devices()[0]]
    log_memory("init", devices)

    max_prompt, max_new = sizes.prompt[1], sizes.new[1]
    engine = engine_for(params, cfg, num_slots=sizes.slots,
                        max_prompt_len=max_prompt, max_new_cap=max_new)
    requests = make_requests(cfg, sizes.requests, sizes.prompt, sizes.new,
                             seed)
    done, wall = serve_requests(engine, requests)
    tokens = sum(f.generated for f in done.values())
    steps = sum(max(f.invocations - 1, 1) for f in done.values())
    log(f"engine: {len(done)} requests, {tokens} tokens in {wall:.1f} s "
        f"wall (a smoke figure, not a metric), mean k̂ {tokens / steps:.3f} "
        f"(random weights: degenerate text, not a trained model's "
        f"acceptance), compiles {engine.compile_counts()}")
    log_memory("serving", devices)
    length = max_prompt + max_new
    fwd = uncached_p1(cfg)
    checked = check_finite(fwd, params, requests, done, length)
    log(f"p1 logits finite at all {checked} served positions")

    http_req = make_requests(cfg, 1, (sizes.prompt[0],) * 2,
                             (sizes.new[0],) * 2, seed + 1)[0]
    reply = serve_http(engine, http_req.prompt, http_req.max_new)
    log(f"HTTP/SSE: /readyz 200, {reply['generated']} tokens streamed, "
        f"equal to the done payload")
    log_memory("HTTP", devices)

    pair = requests[:2]
    p1, drafts = cached_verify(engine, params, pair)
    worst = compare_cached(fwd, params, cfg, pair, p1, drafts, length)
    log(f"cached verify vs uncached forward: max |Δ p1 logit| {worst:.4f} "
        f"(tolerance {LOGIT_TOL})")
    share, divergences = compare_greedy(fwd, params, cfg, pair, done,
                                        length)
    log(f"exact BPD tokens equal to greedy_decode's: {share:.4f}; first "
        f"divergences (rid, token, top-2 margin): {divergences}")
    log_memory("logit checks", devices)
    khats = check_fused_verify(p1, drafts, compiled=compiled)
    log(f"fused verify kernel ({'compiled' if compiled else 'interpreted'}) "
        f"== unfused acceptors; k̂ {khats}")
    return devices


def four_chips(cut, whole, seed, sizes=FOUR_CHIPS):
    """The sharded path on a ("data", "model") = (1, 4) mesh: ``cut`` on
    one device against the mesh, then ``whole`` drawn straight into its
    shardings and served through the engine."""
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(data=1, model=4, require=True)
    tokens = jnp.asarray(np.random.default_rng(seed).integers(
        0, cut.vocab_size, (2, sizes.prompt[0])), jnp.int32)
    worst = compare_sharded(cut, mesh, tokens, seed)
    log(f"{cut.num_layers} layers, one device vs mesh {dict(mesh.shape)}: "
        f"max |Δ prefill p1 logit| {worst:.4f} (tolerance {LOGIT_TOL})")

    t0 = time.monotonic()
    params = M.init_params(jax.random.PRNGKey(seed), whole, mesh)
    jax.block_until_ready(params)
    log(f"whole {whole.name}: {whole.num_layers} layers, "
        f"{count_params(params):,} parameters drawn into their shardings "
        f"in {time.monotonic() - t0:.1f} s")
    engine = engine_for(params, whole, num_slots=sizes.slots,
                        max_prompt_len=sizes.prompt[1],
                        max_new_cap=sizes.new[1], mesh=mesh)
    requests = make_requests(whole, sizes.requests, sizes.prompt, sizes.new,
                             seed)
    done, wall = serve_requests(engine, requests)
    log(f"engine on mesh {dict(mesh.shape)}: {len(done)} requests, "
        f"{sum(f.generated for f in done.values())} tokens in {wall:.1f} s "
        f"wall, compiles {engine.compile_counts()}")
    devices = list(mesh.devices.flat)
    log_memory("serving", devices)
    return devices


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2
    log(f"compile cache: {enable_compile_cache()}")
    compiles = tracing.snapshot()
    t0 = time.monotonic()
    try:
        if args.chips == 4:
            used = four_chips(published_config(ONE_CHIP_LAYERS),
                              published_config(40), args.seed)
        else:
            used = one_chip(published_config(ONE_CHIP_LAYERS), args.seed)
    except Failed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log_compiles(compiles)
    log_memory("all phases", used)
    log(f"all phases passed in {time.monotonic() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
