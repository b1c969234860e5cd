"""Roofline report: aggregates the dry-run JSON records (deliverable g).

Reads experiments/dryrun/*.json (written by repro.launch.dryrun), prints the
per-(arch × shape × mesh) three-term roofline table, and emits
experiments/roofline.csv for EXPERIMENTS.md §Roofline.

Terms (v5e: 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI):
    compute_s    = HLO_FLOPs / (chips * peak)
    memory_s     = HLO_bytes / (chips * hbm_bw)
    collective_s = collective_bytes / (chips * ici_bw)
"""
from __future__ import annotations

import argparse
import glob
import json
import os


PEAK_FLOPS_BF16 = 197e12   # v5e per chip
HBM_BW = 819e9
ICI_BW = 50e9


def fused_verify_estimate(b: int, k: int, vocab: int, *, top_t: int = 1,
                          dtype_bytes: int = 4) -> dict:
    """Analytic roofline for the one-pass accept kernel
    (``kernels.fused_verify``): bytes / FLOP estimates and the v5e memory
    term, for the BENCH_decode.json roofline row.

    The kernel streams the (b·k, V) verification logits exactly once
    (HBM-dominant), carrying an O(top_t) running top-T per row in VMEM;
    the accept scan epilogue touches only (b, k) integers.  The unfused
    path reads the same logits for argmax AND materializes/reads the
    (b, k) comparisons separately — the win is one pass instead of two
    plus kernel-launch fusion, so bytes here are the optimum floor.
    """
    logits_bytes = b * k * vocab * dtype_bytes
    io_bytes = logits_bytes + b * k * 4 * 3 + b * 4 * 2   # props + outputs
    # per element: compare-into-max (1) + top-T merge amortized (~top_t)
    flops = b * k * vocab * (1 + top_t)
    return {
        "bytes": float(io_bytes),
        "flops": float(flops),
        "flops_per_byte": round(flops / io_bytes, 4),
        "v5e_memory_us": round(io_bytes / HBM_BW * 1e6, 2),
        "v5e_compute_us": round(flops / PEAK_FLOPS_BF16 * 1e6, 4),
        "bottleneck": "memory_s",
    }


def recompute_terms(r):
    """Roofline terms from the raw per-device cost-analysis values.

    ``cost_analysis()`` reports the per-device SPMD module, so terms divide
    by single-chip peaks.  Records written by any dryrun version are
    normalized here so the report is always consistent."""
    if r.get("status") != "ok":
        return r
    if "flops_convention" not in r:
        # records written before the convention fix used 3× the standard
        # MODEL_FLOPS (6ND·3 for train, 6ND for inference) — normalize to
        # fwd = 2·N·D, train = 6·N·D.
        r["model_flops"] = r["model_flops"] / 3.0
        r["flops_convention"] = "2nd-fwd-6nd-train"
    terms = {
        "compute_s": r["hlo_flops"] / PEAK_FLOPS_BF16,
        "memory_s": r["hlo_bytes"] / HBM_BW,
        "collective_s": r["collectives"]["total_bytes"] / ICI_BW,
    }
    terms["bottleneck"] = max(
        ("compute_s", "memory_s", "collective_s"), key=terms.get)
    r["roofline"] = terms
    r["useful_flops_ratio"] = (
        r["model_flops"] / (r["hlo_flops"] * r["chips"])
        if r["hlo_flops"] else None)
    return r


def load_records(dryrun_dir: str):
    recs = []
    for path in sorted(glob.glob(os.path.join(dryrun_dir, "*.json"))):
        with open(path) as f:
            r = json.load(f)
        recs.append(recompute_terms(r))
    return recs


def format_row(r) -> str:
    if r["status"] != "ok":
        return (f"{r['arch']:18s} {r['shape']:12s} {r['mesh']:11s} "
                f"{r['status'].upper()}: {r.get('reason', r.get('error', ''))[:60]}")
    t = r["roofline"]
    return (f"{r['arch']:18s} {r['shape']:12s} {r['mesh']:11s} "
            f"C={t['compute_s'] * 1e3:9.3f}ms "
            f"M={t['memory_s'] * 1e3:9.3f}ms "
            f"X={t['collective_s'] * 1e3:9.3f}ms "
            f"dom={t['bottleneck'][:-2]:10s} "
            f"useful={r['useful_flops_ratio']:.3f}" if r.get("useful_flops_ratio")
            else "")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun-dir", default="experiments/dryrun")
    ap.add_argument("--csv", default="experiments/roofline.csv")
    args = ap.parse_args()

    est = fused_verify_estimate(64, 8, 32768)
    print(f"[roofline] fused-verify (b=64 k=8 V=32768): "
          f"{est['bytes'] / 2**20:.1f} MiB, {est['flops'] / 1e6:.1f} MFLOP, "
          f"{est['flops_per_byte']:.2f} FLOP/B -> {est['bottleneck']} "
          f"(v5e mem {est['v5e_memory_us']:.1f} us)")

    recs = load_records(args.dryrun_dir)
    if not recs:
        print("[roofline] no dry-run records found — run "
              "`python -m repro.launch.dryrun --all` first")
        return

    print(f"{'arch':18s} {'shape':12s} {'mesh':11s} roofline terms")
    for r in recs:
        print(format_row(r))

    ok = [r for r in recs if r["status"] == "ok"]
    with open(args.csv, "w") as f:
        f.write("arch,shape,mesh,chips,compute_s,memory_s,collective_s,"
                "bottleneck,hlo_flops,hlo_bytes,collective_bytes,"
                "model_flops,useful_flops_ratio\n")
        for r in ok:
            t = r["roofline"]
            f.write(f"{r['arch']},{r['shape']},{r['mesh']},{r['chips']},"
                    f"{t['compute_s']:.6e},{t['memory_s']:.6e},"
                    f"{t['collective_s']:.6e},{t['bottleneck']},"
                    f"{r['hlo_flops']:.4e},{r['hlo_bytes']:.4e},"
                    f"{r['collectives']['total_bytes']:.4e},"
                    f"{r['model_flops']:.4e},"
                    f"{r['useful_flops_ratio'] or 0:.4f}\n")
    print(f"\n[roofline] {len(ok)} OK records -> {args.csv}")

    doms = {}
    for r in ok:
        doms[r["roofline"]["bottleneck"]] = doms.get(
            r["roofline"]["bottleneck"], 0) + 1
    print(f"[roofline] bottleneck distribution: {doms}")


if __name__ == "__main__":
    main()
