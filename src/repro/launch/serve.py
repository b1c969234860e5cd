"""Serving launcher: restore a checkpoint and decode request batches with
blockwise parallel decoding.

    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-8b --smoke \
        --ckpt-dir /tmp/ckpt --batch 4 --max-new 32 \
        [--criterion topk --top-k 2] [--policy topk_tree] [--sched sjf] \
        [--policy draft_model --draft-arch granite-3-8b \
         --draft-ckpt /tmp/draft-ckpt] \
        [--engine --policies exact=2,topk_tree=2] \
        [--cache-backend paged --page-size 16]

``--cache-backend paged`` swaps the dense per-slot KV rows for the paged
cache (fixed-size pages, per-slot block tables, CoW prefix sharing); in
engine mode admissions allocate pages from a shared pool and identical
prompt prefixes are stored once.  Token-identical to dense.

``--policy`` selects the SESSION-DEFAULT decode policy (drafter ×
acceptor × block schedule, see README "Decode policies"); unset, the
legacy ``--criterion`` alias applies.  ``--sched`` picks the engine's
admission order (fcfs/sjf).

``--policies name=slots,name=slots`` (engine mode) partitions the slot
slab into per-policy slot groups and makes the decode policy a
PER-REQUEST field: each generated request carries a policy sampled from
the configured groups (``Request.policy``; unset requests fall back to
the ``--policy`` session default), and the engine schedules each group
with its own compile-once step.

``--policy draft_model`` serves with the speculative draft-model drafter:
a second (small) model — the ``--draft-arch`` smoke config, restored from
``--draft-ckpt`` when given — proposes each block autoregressively
through an auxiliary ``ModelBundle``, and the primary model verifies
losslessly.  Both modes (static batch and ``--engine``) thread the bundle
through the same ``DecodeSession``.

Runs the prefill + serve_step loop (the same entry points the multi-pod
dry-run lowers).  By default the arch is served at its published widths
and dtypes, with parameters drawn in one jitted program (straight into
their shardings under a mesh); ``--smoke`` serves the reduced smoke config
in float32 instead, the size for CPU runs and CI.

``--engine`` switches to the continuous-batching engine (repro.serving):
2×batch mixed-length requests are scheduled through ``--batch`` slots with
mid-flight admission, printing per-request stats and the aggregate
tokens/sec + latency summary.

``--mesh-data N --mesh-model M`` runs either mode through a mesh-backed
``DecodeSession``: params placed by ``sharding.policy.param_shardings``,
the decode state / slot batch sharded over the data axis and tensors over
the model axis.  On a CPU host prefix the command with
``XLA_FLAGS=--xla_force_host_platform_device_count=<N*M>``.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import latest_step, restore
from repro.config import DecodeConfig, get_config
from repro.data.synthetic import MarkovLM
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="serve the arch's reduced smoke config in float32 "
                         "(CPU runs, CI) instead of its published widths "
                         "and dtypes")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--block-k", type=int, default=0)
    ap.add_argument("--criterion", default="exact",
                    choices=["exact", "topk", "distance"],
                    help="legacy alias for --policy (the three acceptor "
                         "names are registered policies); prefer --policy")
    ap.add_argument("--policy", default="",
                    help="decode policy name (drafter × acceptor × "
                         "schedule; see repro.config.list_policies()); "
                         "empty = the --criterion legacy alias")
    ap.add_argument("--cache-backend", default="dense",
                    choices=["dense", "paged"],
                    help="KV cache layout: dense per-slot rows, or paged "
                         "(fixed-size pages + block tables + CoW prefix "
                         "sharing; engine mode allocates pages per request)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (multiple of 8; paged only)")
    ap.add_argument("--top-k", type=int, default=2)
    ap.add_argument("--epsilon", type=float, default=2.0)
    ap.add_argument("--image-height", type=int, default=0,
                    help="2-D raster rows for the locality policy "
                         "(--policy locality / --policies locality=N): "
                         "the token stream is an image serialized in the "
                         "progressive-lattice order")
    ap.add_argument("--image-width", type=int, default=0,
                    help="2-D raster cols for the locality policy")
    ap.add_argument("--locality-stride", type=int, default=4,
                    help="coarse-lattice stride of the locality order "
                         "(power of two)")
    ap.add_argument("--draft-arch", default=None,
                    help="arch of the speculative draft model (smoke "
                         "config; --policy draft_model); defaults to "
                         "--arch — the draft vocab must match the primary")
    ap.add_argument("--draft-ckpt", default=None,
                    help="checkpoint dir for the draft model's params "
                         "(unset: randomly initialized — lossless but "
                         "slow, demo only)")
    ap.add_argument("--fused-verify", action="store_true",
                    help="route block acceptance through the one-pass "
                         "Pallas accept kernel (kernels/fused_verify; "
                         "token-identical opt-in)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--engine", action="store_true",
                    help="serve through the continuous-batching engine "
                         "(slots + admission) instead of one static batch")
    ap.add_argument("--policies", default="",
                    help="engine-mode per-policy slot groups, e.g. "
                         "'exact=2,topk_tree=2' (must partition --batch); "
                         "requests then carry a per-request policy sampled "
                         "from the groups.  Empty: one group running the "
                         "--policy/--criterion session default")
    ap.add_argument("--sched", default="fcfs", choices=["fcfs", "sjf"],
                    help="engine admission policy (scheduler)")
    ap.add_argument("--http", action="store_true",
                    help="serve the continuous-batching engine over "
                         "HTTP/SSE (POST /v1/generate streams token "
                         "events; /healthz /readyz /metrics) instead of "
                         "replaying a synthetic workload")
    ap.add_argument("--host", default="127.0.0.1",
                    help="--http bind address")
    ap.add_argument("--port", type=int, default=8000,
                    help="--http bind port (0 = ephemeral, printed on "
                         "startup)")
    ap.add_argument("--max-queue", type=int, default=16,
                    help="--http admission queue bound: requests beyond "
                         "it are rejected with 429 + Retry-After")
    ap.add_argument("--http-demo", action="store_true",
                    help="with --http: boot the server, stream ONE "
                         "self-request end-to-end (printing the SSE "
                         "events), check /healthz + /readyz, then exit — "
                         "the CI smoke mode")
    ap.add_argument("--mesh-data", type=int, default=0,
                    help="data-parallel shards (0 = no mesh, single device)")
    ap.add_argument("--mesh-model", type=int, default=1,
                    help="tensor-parallel shards over the model axis")
    ap.add_argument("--mesh-pod", type=int, default=1,
                    help="pod-parallel shards (production "
                         "('pod','data','model') mesh shape; prefill "
                         "workers shard over the pod axis, the slot slab "
                         "over pod×data)")
    ap.add_argument("--prefill-slots", type=int, default=0,
                    help="disaggregated prefill/decode: prompts prefilled "
                         "per prefill-worker forward, handed to decode "
                         "groups through the bounded KV-handoff queue "
                         "(0 = unified engine, admission prefills inline)")
    ap.add_argument("--handoff-cap", type=int, default=0,
                    help="bound on requests staged for / parked in the "
                         "KV-handoff queue (0 = auto)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    if args.smoke:
        cfg = cfg.replace(dtype="float32")
    if cfg.is_encoder_only:
        raise SystemExit(f"{args.arch} is encoder-only — no decode path")

    mesh = None
    if args.mesh_data > 0:
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(args.mesh_data, args.mesh_model,
                              pod=args.mesh_pod, require=True)
        print(f"[serve] mesh {dict(mesh.shape)} over {mesh.size} devices")
    params = M.init_params(jax.random.PRNGKey(args.seed), cfg, mesh)
    if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        params, extra = restore(args.ckpt_dir, params)
        print(f"[serve] restored step {latest_step(args.ckpt_dir)} "
              f"({extra.get('arch')})")

    # --criterion is resolved here to a registered policy name so the
    # deprecated criterion-string fallback never fires downstream
    dec = DecodeConfig(max_new_tokens=args.max_new,
                       block_k=args.block_k or cfg.bpd_k,
                       policy=args.policy or args.criterion,
                       top_k=args.top_k, epsilon=args.epsilon,
                       cache_backend=args.cache_backend,
                       page_size=args.page_size,
                       fused_verify=args.fused_verify,
                       image_height=args.image_height,
                       image_width=args.image_width,
                       locality_stride=args.locality_stride)
    task = MarkovLM(vocab=min(cfg.vocab_size, 256), temperature=0.2,
                    seed=args.seed)
    prompts = jnp.asarray(task.sample(np.random.default_rng(args.seed + 1),
                                      args.batch, args.prompt_len))
    batch = {"tokens": prompts}
    if cfg.modality == "vision_text":
        batch["patch_embeds"] = jnp.zeros((args.batch, 4, cfg.d_model),
                                          jnp.float32)

    groups = parse_policy_groups(args.policies)
    if groups and not (args.engine or args.http):
        raise SystemExit("--policies configures per-request slot groups in "
                         "the continuous-batching engine: add --engine "
                         "(or --http)")
    bundles = draft_bundle(cfg, args, groups, mesh)

    if args.http:
        serve_http(params, cfg, dec, args, mesh=mesh, bundles=bundles,
                   groups=groups)
        return

    if args.engine:
        serve_engine(params, cfg, dec, args, task, mesh=mesh,
                     bundles=bundles, groups=groups)
        return

    # static batch through the same session layer the engine uses —
    # jitted once (with explicit shardings when a mesh is given)
    from repro.serving import DecodeSession
    sess = DecodeSession(params, cfg, dec, mesh=mesh, jit=True,
                         bundles=bundles)
    sess.decode(batch)  # compile
    t0 = time.time()
    toks, stats = sess.decode(batch)
    jax.block_until_ready(toks)
    dt = time.time() - t0

    print(f"[serve] {args.batch} requests, {args.max_new} tokens each, "
          f"policy={sess.policy.name}")
    print(f"[serve] mean accepted block size k̂ = "
          f"{float(stats['mean_accepted']):.2f}  "
          f"invocations = {int(stats['invocations'])} "
          f"(greedy would need {args.max_new + 1})  wall = {dt * 1e3:.0f}ms")
    for r in range(args.batch):
        n = int(stats["text_len"][r])
        out = [int(x) for x in np.asarray(toks[r, args.prompt_len:n])]
        print(f"    row {r}: {out}")


def parse_policy_groups(spec: str):
    """'exact=2,topk_tree=2' -> {"exact": 2, "topk_tree": 2} (None when
    empty).  Every error names its fix here, at the flag, instead of
    surfacing later as an EngineConfig/registry failure mid-compile.
    Slot counts must partition --batch; the engine validates that part."""
    if not spec:
        return None
    from repro.config import list_policies

    known = list_policies()
    groups = {}
    for part in spec.split(","):
        name, sep, n = part.strip().partition("=")
        if not sep or not name or not n.lstrip("+-").isdigit():
            raise SystemExit(f"--policies entry {part!r}: expected "
                             f"name=slots, e.g. exact=2")
        if name not in known:
            raise SystemExit(f"--policies names unknown policy {name!r}: "
                             f"registered policies are "
                             f"{', '.join(sorted(known))}")
        if name in groups:
            raise SystemExit(f"--policies names {name!r} twice: one slot "
                             f"group per policy — merge the counts into a "
                             f"single {name}=n entry")
        slots = int(n)
        if slots <= 0:
            raise SystemExit(f"--policies entry {part.strip()!r}: slot "
                             f"count must be a positive integer (every "
                             f"group needs at least one slot)")
        groups[name] = slots
    return groups


def draft_bundle(cfg, args, groups=None, mesh=None):
    """Build the auxiliary draft ``ModelBundle`` when any served policy is
    draft_model (None otherwise): the --draft-arch config (default: the
    primary arch; smoke-sized under --smoke), restored from --draft-ckpt
    when given."""
    if args.policy != "draft_model" and "draft_model" not in (groups or {}):
        return None
    from repro.core.bundle import ModelBundle

    dcfg = get_config(args.draft_arch or args.arch,
                      smoke=args.smoke).replace(bpd_enabled=False)
    if args.smoke:
        dcfg = dcfg.replace(dtype="float32")
    dparams = M.init_params(jax.random.PRNGKey(args.seed + 7), dcfg, mesh)
    if args.draft_ckpt and latest_step(args.draft_ckpt) is not None:
        dparams, extra = restore(args.draft_ckpt, dparams)
        print(f"[serve] draft model: restored step "
              f"{latest_step(args.draft_ckpt)} ({extra.get('arch')})")
    else:
        print(f"[serve] draft model: {dcfg.name} (randomly initialized — "
              f"lossless, but expect k̂ ≈ 1; pass --draft-ckpt for a real "
              f"draft)")
    return {"draft": ModelBundle(dparams, dcfg)}


def serve_engine(params, cfg, dec, args, task, *, mesh=None, bundles=None,
                 groups=None):
    """Mixed-length request traffic through the continuous-batching engine
    — with ``groups``, mixed-POLICY traffic over per-policy slot groups."""
    from repro.serving import (ContinuousBatchingEngine, EngineConfig,
                               Request, Scheduler, aggregate_stats)

    ecfg = EngineConfig(num_slots=args.batch,
                        max_prompt_len=args.prompt_len,
                        max_new_cap=args.max_new)
    engine = ContinuousBatchingEngine(params, cfg, dec, ecfg, mesh=mesh,
                                      bundles=bundles, policies=groups)
    sched = Scheduler(engine, policy=args.sched)

    rng = np.random.default_rng(args.seed + 2)
    names = engine.policy_names()
    n = 2 * args.batch
    for rid in range(n):
        plen = int(rng.integers(max(args.prompt_len // 2, 1),
                                args.prompt_len + 1))
        sched.submit(Request(
            rid=rid, prompt=task.sample(rng, 1, plen)[0],
            max_new=int(rng.integers(max(args.max_new // 4, 1),
                                     args.max_new + 1)),
            # the per-request policy field: sampled over the slot groups
            # (None when the engine runs one default group)
            policy=str(rng.choice(names)) if groups else None))

    t0 = time.time()
    finished = sched.run()
    wall = time.time() - t0
    stats = aggregate_stats(finished, wall)

    print(f"[serve] engine: {n} requests over {args.batch} slots "
          f"(sched={args.sched}, "
          f"{'groups=' + str(groups) if groups else 'policy=' + engine.policy.name})")
    print(f"[serve] {stats['total_tokens']} tokens in "
          f"{stats['total_invocations']} invocations, "
          f"{stats['tokens_per_sec']:.0f} tok/s, "
          f"p50 {stats['latency_p50_s'] * 1e3:.0f}ms / "
          f"p95 {stats['latency_p95_s'] * 1e3:.0f}ms, "
          f"compile {engine.compile_counts()}")
    for f in sorted(finished, key=lambda f: f.rid):
        print(f"    req {f.rid} [{f.policy}]: k̂={f.mean_accepted:.2f} "
              f"gen={f.generated} inv={f.invocations} "
              f"out={[int(x) for x in f.tokens]}")


def serve_http(params, cfg, dec, args, *, mesh=None, bundles=None,
               groups=None):
    """Serve the engine over HTTP/SSE (see serving.server for the routes);
    ``--http-demo`` instead streams one self-request and exits (CI smoke)."""
    import asyncio

    from repro.serving import (ContinuousBatchingEngine, EngineConfig,
                               Frontend, HTTPServer, Scheduler)

    ecfg = EngineConfig(num_slots=args.batch,
                        max_prompt_len=args.prompt_len,
                        max_new_cap=args.max_new,
                        prefill_slots=args.prefill_slots,
                        handoff_cap=args.handoff_cap)
    engine = ContinuousBatchingEngine(params, cfg, dec, ecfg, mesh=mesh,
                                      bundles=bundles, policies=groups)
    sched = Scheduler(engine, policy=args.sched)
    srv = HTTPServer(Frontend(sched, max_queue=args.max_queue),
                     host=args.host, port=args.port)

    async def run():
        import signal

        await srv.start()
        # SIGTERM → graceful drain: stop admission, finish what's in
        # flight (SSE tails flush), close the listener, exit 0 — the same
        # path POST /drain takes
        loop = asyncio.get_running_loop()
        try:
            loop.add_signal_handler(signal.SIGTERM, srv.begin_drain)
            loop.add_signal_handler(signal.SIGINT, srv.begin_drain)
        except NotImplementedError:    # non-Unix event loops
            pass
        mode = (f"disaggregated prefill_slots={args.prefill_slots}"
                if args.prefill_slots else "unified")
        print(f"[serve] http on {srv.host}:{srv.port} — POST /v1/generate "
              f"/drain, GET /healthz /readyz /metrics "
              f"(slots={args.batch}, sched={args.sched}, "
              f"max_queue={args.max_queue}, {mode})", flush=True)
        if args.http_demo:
            await _http_demo(srv)
            await srv.stop()
        else:
            await srv.serve_forever()
            print("[serve] drained — exiting", flush=True)

    asyncio.run(run())


async def stream_one(srv, prompt, max_new: int):
    """Stream one request end to end against a live server, over a real
    socket: asserts green health checks and SSE token + done events whose
    tokens agree, raising SystemExit on any miss.  Returns ``(raw SSE
    text, done payload)``."""
    import asyncio
    import json

    async def get(path):
        r, w = await asyncio.open_connection(srv.host, srv.port)
        w.write(f"GET {path} HTTP/1.1\r\nHost: {srv.host}\r\n\r\n".encode())
        await w.drain()
        data = await r.read()
        w.close()
        return data.decode()

    for path in ("/healthz", "/readyz"):
        status = (await get(path)).splitlines()[0]
        print(f"[serve] {path} -> {status}")
        if "200" not in status:
            raise SystemExit(f"stream_one: {path} returned {status!r}")

    body = json.dumps({"prompt": [int(t) for t in prompt],
                       "max_new": int(max_new), "stream": True}).encode()
    r, w = await asyncio.open_connection(srv.host, srv.port)
    w.write(b"POST /v1/generate HTTP/1.1\r\n"
            + f"Host: {srv.host}\r\n".encode()
            + f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
    await w.drain()
    raw = (await r.read()).decode()
    w.close()
    events, cur = [], None
    for ln in raw.splitlines():
        if ln.startswith("event: "):
            cur = ln[7:]
        elif ln.startswith("data: ") and cur is not None:
            events.append((cur, json.loads(ln[6:])))
    tokens = [t for kind, d in events if kind == "token"
              for t in d["tokens"]]
    dones = [d for kind, d in events if kind == "done"]
    if not tokens or not dones:
        raise SystemExit("stream_one: stream missing token/done SSE events")
    done = dones[0]
    # the done payload repeats the full stream — they must agree exactly
    if tokens != done["tokens"]:
        raise SystemExit("stream_one: streamed tokens disagree with the "
                         "done payload")
    return raw, done


async def _http_demo(srv):
    """The CI server-smoke contract: one streamed self-request, printed."""
    raw, done = await stream_one(srv, [5, 6, 7, 8], 12)
    print("[serve] SSE stream:")
    print("    " + "\n    ".join(ln for ln in raw.splitlines() if ln))
    print(f"[serve] demo ok: {done['generated']} tokens streamed, "
          f"k̂={done['mean_accepted']:.2f}")


if __name__ == "__main__":
    main()
