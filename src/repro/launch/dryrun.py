"""Multi-pod dry-run: prove the distribution config is coherent without
hardware.

For every (architecture × input shape × mesh) combination this lowers and
compiles the corresponding step function on the production mesh
(16×16 = 256 chips single-pod; 2×16×16 = 512 chips multi-pod), prints
``memory_analysis()`` / ``cost_analysis()``, extracts the collective traffic
from the compiled HLO, and writes one JSON record per combination for the
roofline analysis (EXPERIMENTS.md §Dry-run / §Roofline).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch granite-3-8b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import json
import os
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config import (
    INPUT_SHAPES,
    DecodeConfig,
    ModelConfig,
    TrainConfig,
    get_config,
)
from repro.launch import steps as steps_lib
from repro.launch.hlo import collective_bytes
from repro.launch.mesh import (
    HBM_BW,
    ICI_BW,
    PEAK_FLOPS_BF16,
    make_production_mesh,
)
from repro.models import model as model_lib
from repro.optim import optimizer_init
from repro.sharding import (
    batch_specs,
    named,
    param_specs,
    state_specs,
)

def active_params(cfg: ModelConfig, n_total: int) -> int:
    """Active parameter count for MODEL_FLOPS (MoE: routed experts scaled by
    top-k/E)."""
    if cfg.mlp_type != "moe":
        return n_total
    ff = cfg.d_ff
    gated = 3 if cfg.activation in ("silu", "geglu") else 2
    expert_params = cfg.num_layers * cfg.num_experts * gated * cfg.d_model * ff
    active_expert = expert_params * cfg.num_experts_per_tok / cfg.num_experts
    return int(n_total - expert_params + active_expert)


def model_flops(cfg: ModelConfig, n_active: int, tokens: int) -> float:
    return 6.0 * n_active * tokens


# ---------------------------------------------------------------------------


def build_lowering(cfg: ModelConfig, shape_name: str, mesh, *,
                   serve_bf16: bool = False, remat: bool = False):
    """Construct (jitted_fn, arg_structs, arg_shardings) for one combo.

    serve_bf16 casts the stored parameters to bf16 for the inference kinds
    (standard serving practice — halves weight residency and read traffic;
    measured as a §Perf iteration, baseline keeps the training dtype)."""
    spec = INPUT_SHAPES[shape_name]
    kind = spec["kind"]
    if serve_bf16 and kind != "train":
        cfg = cfg.replace(param_dtype="bfloat16")
    if remat and kind == "train":
        cfg = cfg.replace(remat=True)
    b, s = spec["global_batch"], spec["seq_len"]
    key = jax.random.PRNGKey(0)
    params_struct = jax.eval_shape(lambda: model_lib.init(key, cfg))
    p_specs = param_specs(params_struct, mesh)
    p_shard = named(mesh, p_specs)

    # long-context PREFILL uses the chunked (flash-style) attention so the
    # (Sq, Sk) score tensor never materializes.  Decode keeps the plain
    # einsum: with q = block_k tiny the score tensor is (B, H, k, L) — small —
    # and chunk-reshaping a length-sharded KV cache would force GSPMD to
    # replicate it (measured: 87 GB of involuntary all-gather per step).
    kv_chunk = 2048 if (s > 8192 and kind != "decode") else 0

    batch = steps_lib.input_specs(cfg, shape_name)
    b_specs = batch_specs(mesh, batch)
    b_shard = named(mesh, b_specs)

    if kind == "train":
        tc = TrainConfig(global_batch=b, seq_len=s)
        opt_struct = jax.eval_shape(lambda p: optimizer_init(p, tc), params_struct)
        # optimizer state mirrors param sharding (mu/nu/v); scalars replicated
        o_shard = {
            k: (named(mesh, param_specs(v, mesh))
                if k in ("mu", "nu", "v") else NamedSharding(mesh, P()))
            for k, v in opt_struct.items()
        }
        key_struct = jax.ShapeDtypeStruct((2,), jnp.uint32)
        fn = steps_lib.make_train_step(cfg, tc)
        jitted = jax.jit(
            fn,
            in_shardings=(p_shard, o_shard, b_shard, NamedSharding(mesh, P())),
            out_shardings=(p_shard, o_shard, None),
        )
        args = (params_struct, opt_struct, batch, key_struct)
        return jitted, args

    dec = DecodeConfig(max_new_tokens=64, block_k=cfg.bpd_k if cfg.bpd_enabled else 1)

    if kind == "prefill":
        fn = steps_lib.make_prefill_step(cfg, dec, kv_chunk=kv_chunk)
        if cfg.is_encoder_only:
            jitted = jax.jit(fn, in_shardings=(p_shard, b_shard))
            return jitted, (params_struct, batch)
        jitted = jax.jit(fn, in_shardings=(p_shard, b_shard))
        return jitted, (params_struct, batch)

    # decode: one BPD iteration (serve_step) — loop-state specs come from the
    # same sharding.policy.state_specs builder the DecodeSession uses
    state_struct = steps_lib.serve_state_struct(cfg, dec, batch=b, seq_len=s,
                                                max_new=64)
    st_specs = state_specs(cfg, state_struct, mesh, batch_size=b)
    st_shard = named(mesh, st_specs)
    fn = steps_lib.make_serve_step(cfg, dec, seq_len=s, max_new=64,
                                   kv_chunk=kv_chunk)
    jitted = jax.jit(fn, in_shardings=(p_shard, st_shard),
                     out_shardings=st_shard)
    return jitted, (params_struct, state_struct)


# ---------------------------------------------------------------------------


def run_combo(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
              *, verbose: bool = True, serve_bf16: bool = False,
              remat: bool = False) -> Optional[Dict]:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    tag = f"{arch}_{shape_name}_{mesh_name}"
    if serve_bf16:
        tag += "_bf16serve"
    if remat:
        tag += "_remat"
    cfg = steps_lib.adapt_config(get_config(arch), shape_name)
    if cfg is None:
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "status": "skipped",
               "reason": "encoder-only: no autoregressive decode"}
        _write(out_dir, tag, rec)
        if verbose:
            print(f"[dryrun] {tag}: SKIPPED (encoder-only decode)")
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(mesh.devices.shape))
    spec = INPUT_SHAPES[shape_name]
    t0 = time.time()
    with jax.set_mesh(mesh):
        jitted, args = build_lowering(cfg, shape_name, mesh,
                                      serve_bf16=serve_bf16, remat=remat)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)

    n_params = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(
        jax.eval_shape(lambda: model_lib.init(jax.random.PRNGKey(0), cfg))))
    n_active = active_params(cfg, n_params)
    # convention: fwd = 2*N*D, fwd+bwd (train) = 6*N*D
    if spec["kind"] == "train":
        tokens = spec["global_batch"] * spec["seq_len"]
        mult = 6.0
    elif spec["kind"] == "prefill":
        tokens = spec["global_batch"] * spec["seq_len"]
        mult = 2.0
    else:
        tokens = spec["global_batch"] * (cfg.bpd_k if cfg.bpd_enabled else 1)
        mult = 2.0
    m_flops = mult * n_active * tokens

    # cost_analysis() reports the PER-DEVICE SPMD module (verified: a 4-way
    # sharded matmul reports 1/4 of the full flops), so the roofline terms
    # divide by single-chip peak numbers, not by the mesh size.
    hlo_flops = float(cost.get("flops", 0.0)) if cost else 0.0
    hlo_bytes = float(cost.get("bytes accessed", 0.0)) if cost else 0.0

    compute_s = hlo_flops / PEAK_FLOPS_BF16
    memory_s = hlo_bytes / HBM_BW
    collective_s = coll["total_bytes"] / ICI_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    bottleneck = max(terms, key=terms.get)

    def _mem_attr(name):
        try:
            return int(getattr(mem, name))
        except Exception:
            return None

    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "status": "ok",
        "chips": n_chips,
        "kind": spec["kind"],
        "sliding_window": cfg.sliding_window,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "n_params": n_params, "n_active_params": n_active,
        "hlo_flops": hlo_flops, "hlo_bytes": hlo_bytes,
        "model_flops": m_flops,
        "flops_convention": "2nd-fwd-6nd-train",
        "useful_flops_ratio": (m_flops / (hlo_flops * n_chips))
        if hlo_flops else None,
        "collectives": coll,
        "roofline": dict(terms, bottleneck=bottleneck),
        "memory_analysis": {
            "argument_size_bytes": _mem_attr("argument_size_in_bytes"),
            "output_size_bytes": _mem_attr("output_size_in_bytes"),
            "temp_size_bytes": _mem_attr("temp_size_in_bytes"),
            "generated_code_size_bytes": _mem_attr("generated_code_size_in_bytes"),
        },
    }
    _write(out_dir, tag, rec)
    if verbose:
        print(f"[dryrun] {tag}: OK chips={n_chips} "
              f"flops={hlo_flops:.3e} bytes={hlo_bytes:.3e} "
              f"coll={coll['total_bytes']:.3e}B "
              f"roofline={bottleneck} "
              f"(C={compute_s*1e3:.2f}ms M={memory_s*1e3:.2f}ms "
              f"X={collective_s*1e3:.2f}ms) "
              f"lower={t_lower:.1f}s compile={t_compile:.1f}s")
        print(f"  memory_analysis: {rec['memory_analysis']}")
    return rec


def _write(out_dir: str, tag: str, rec: Dict) -> None:
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, tag + ".json"), "w") as f:
        json.dump(rec, f, indent=2)


def main() -> None:
    # 512 placeholder devices for the production mesh; expensive LLVM codegen
    # passes disabled (pure CPU-backend compile-time saving — verified to
    # leave cost_analysis flops/bytes and the HLO collectives unchanged).
    # Set here, before the first device query initializes the backend, and
    # never on import.
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                               "--xla_llvm_disable_expensive_passes=true")
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--serve-bf16", action="store_true",
                    help="lower inference kinds with bf16 params (§Perf #2)")
    ap.add_argument("--remat", action="store_true",
                    help="per-block activation checkpointing for train (§Perf #4)")
    args = ap.parse_args()

    from repro.configs import ASSIGNED

    archs = ASSIGNED if (args.all or args.arch is None) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    failures = []
    for arch in archs:
        for shape_name in shapes:
            for mp in meshes:
                mesh_name = "pod2x16x16" if mp else "pod16x16"
                tag = f"{arch}_{shape_name}_{mesh_name}"
                path = os.path.join(args.out, tag + ".json")
                if args.skip_existing and os.path.exists(path):
                    with open(path) as f:
                        if json.load(f).get("status") in ("ok", "skipped"):
                            print(f"[dryrun] {tag}: cached")
                            continue
                try:
                    run_combo(arch, shape_name, mp, args.out,
                              serve_bf16=args.serve_bf16, remat=args.remat)
                except Exception as e:  # noqa: BLE001
                    traceback.print_exc()
                    failures.append(tag)
                    _write(args.out, tag,
                           {"arch": arch, "shape": shape_name,
                            "mesh": mesh_name, "status": "error",
                            "error": f"{type(e).__name__}: {e}"})
    if failures:
        print(f"FAILURES ({len(failures)}): {failures}")
        raise SystemExit(1)
    print("dry-run complete: all combinations lowered and compiled")


if __name__ == "__main__":
    main()
