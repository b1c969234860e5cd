"""Render EXPERIMENTS.md sections from the JSON artifacts.

Replaces the <!-- TABLE1 --> / <!-- TABLE2 --> / <!-- TABLE4 --> /
<!-- DRYRUN --> / <!-- ROOFLINE --> / <!-- CLAIMS --> markers with markdown
tables generated from experiments/*.json and experiments/dryrun/*.json.

    PYTHONPATH=src:. python -m benchmarks.report
"""
from __future__ import annotations

import json
import os
import re

from benchmarks.roofline import load_records

EXP = "EXPERIMENTS.md"


def _load(path):
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def table1_md(res) -> str:
    if not res:
        return "_table1.json not present — run `python -m benchmarks.table1_block_size`_"
    ks = sorted({int(k.split("_k")[-1]) for k in res if "_k" in k})
    rows = ["| k | Regular | Distillation | Fine Tuning | Both | Both top-2 | Both top-3 |",
            "|---|---|---|---|---|---|---|"]
    for k in ks:
        def cell(name):
            r = res.get(f"{name}_k{k}")
            return (f"{r['accuracy']:.3f} / {r['mean_accepted']:.2f}"
                    if r else "—")
        rows.append(f"| {k} | {cell('regular')} | {cell('distill')} | "
                    f"{cell('finetune')} | {cell('both')} | "
                    f"{cell('both_top2')} | {cell('both_top3')} |")
    rows.append("")
    rows.append("Cell = token-accuracy vs clean gold (BLEU analog) / mean "
                "accepted block size k̂.  k = 1 rows are the greedy "
                "baselines (regular "
                f"{res['regular_k1']['accuracy']:.3f}, teacher "
                f"{res['distill_k1']['accuracy']:.3f}).")
    return "\n".join(rows)


def table2_md(res) -> str:
    if not res:
        return "_table2.json not present — run `python -m benchmarks.table2_distance`_"
    ks = sorted({int(k.split("_k")[-1]) for k in res if "_k" in k and not k.endswith("k1")})
    rows = ["| k | Regular | Approximate (ε=2) | Fine Tuning | Both |",
            "|---|---|---|---|---|"]
    for k in ks:
        def cell(name):
            r = res.get(f"{name}_k{k}")
            return (f"{r['mean_accepted']:.2f} (MAE {r['mae']:.1f})"
                    if r else "—")
        rows.append(f"| {k} | {cell('regular')} | {cell('approximate')} | "
                    f"{cell('finetune')} | {cell('both')} |")
    rows.append("")
    rows.append("Cell = mean accepted block size k̂ (larger = fewer decode "
                "iterations); MAE = reconstruction error vs the true curve.")
    return "\n".join(rows)


def table4_md(res) -> str:
    if not res:
        return "_table4.json not present — run `python -m benchmarks.table4_wallclock`_"
    rows = ["| k | mean k̂ (iteration speedup) | wall-clock speedup (CPU) | accuracy |",
            "|---|---|---|---|"]
    for key in sorted(res, key=lambda s: int(s[1:])):
        r = res[key]
        rows.append(f"| {key[1:]} | {r['mean_accepted']:.2f} | "
                    f"{r['wallclock_speedup']:.2f}x | {r['accuracy']:.3f} |")
    rows.append("")
    rows.append("CPU wall-clock serializes the verify substep, so the "
                "measured speedup is a LOWER bound on parallel-hardware "
                "speedup; the iteration column is hardware-independent "
                "(the paper's Fig. 4 x-axis).")
    return "\n".join(rows)


def claims_md(t1, t2, t4) -> str:
    if not (t1 and t2 and t4):
        return "_pending benchmark runs_"
    out = []

    def khat(res, name, k):
        r = res.get(f"{name}_k{k}")
        return r["mean_accepted"] if r else float("nan")

    ks = sorted({int(k.split("_k")[-1]) for k in t1 if k.startswith("regular_k")
                 and k != "regular_k1"})
    kb = 2 if 2 in ks else min(ks)   # scale-valid regime (see §Negative #2/#3)
    k_hi = max(ks)
    acc_reg = t1["regular_k1"]["accuracy"]
    out.append(f"* **Frozen heads speed decoding at zero quality cost**: "
               f"regular k̂ ≈ "
               f"{khat(t1, 'regular', k_hi):.2f} at every k with accuracy "
               f"pinned at the baseline {acc_reg:.3f} — the paper's central "
               f"frozen-setting claim (their k̂ saturates at 1.76).")
    out.append(f"* **Fine-tuning raises k̂ beyond frozen** (Table 1, k={kb}): "
               f"regular {khat(t1, 'regular', kb):.2f} < fine-tune "
               f"{khat(t1, 'finetune', kb):.2f}, accuracy "
               f"{t1[f'finetune_k{kb}']['accuracy']:.3f} vs baseline "
               f"{acc_reg:.3f} — the paper's FT effect.  At k ≥ 6 the "
               f"shared-trunk gradient conflict overwhelms the tiny repro "
               f"model (documented in §Negative #2): FT accuracy falls to "
               f"{t1[f'finetune_k{k_hi}']['accuracy']:.3f}, a steeper "
               f"version of the paper's own FT degradation (25.8 → 24.3 "
               f"BLEU at k=8).")
    out.append(f"* **Distillation recovers FT quality**: at k=6, fine-tune "
               f"accuracy {t1['finetune_k6']['accuracy']:.3f} vs both "
               f"{t1['both_k6']['accuracy']:.3f} — the paper's "
               f"distillation-lessens-the-drop effect (their 24.7 vs 26.2 "
               f"BLEU at k=6)." if "finetune_k6" in t1 else "")
    out.append(f"* **Top-k acceptance trades quality for k̂** (§5.1): at "
               f"k={kb} exact {t1[f'both_k{kb}']['accuracy']:.3f}/"
               f"{khat(t1, 'both', kb):.2f} vs top-2 "
               f"{t1[f'both_top2_k{kb}']['accuracy']:.3f}/"
               f"{khat(t1, 'both_top2', kb):.2f}."
               if f"both_top2_k{kb}" in t1 else "")
    t2k = max(int(k.split("_k")[-1]) for k in t2 if "_k" in k and not k.endswith("k1"))
    out.append(f"* **Ordinal task needs approximate acceptance + fine-tuning "
               f"compounded** (Table 2): at k={t2k} regular "
               f"{khat(t2, 'regular', t2k):.2f} / approx "
               f"{khat(t2, 'approximate', t2k):.2f} / FT "
               f"{khat(t2, 'finetune', t2k):.2f} / both "
               f"{khat(t2, 'both', t2k):.2f} — the paper's Table 2 ordering "
               f"(1.09 / 1.40 / 2.04 / 6.79 at k=10).")
    speeds = [(int(k[1:]), v["wallclock_speedup"]) for k, v in t4.items()]
    speeds.sort()
    out.append(f"* **Iteration reduction is monotone in k; wall-clock is "
               f"not** (Fig. 4): khat "
               f"{[round(t4[f'k{k}']['mean_accepted'], 2) for k, _ in speeds]}"
               f" vs CPU wall-clock {[round(s, 2) for _, s in speeds]}x for "
               f"k={[k for k, _ in speeds]}.")
    return "\n".join(out)


def kernels_md(bench) -> str:
    """Kernel timing rows from BENCH_decode.json.

    Rows with the ``_interp`` suffix are Pallas interpret-mode timings
    (kernel body run per grid step through the XLA interpreter on CPU):
    they establish correctness cost only, and are rendered in their own
    column — NEVER as a ratio against ``_ref`` or compiled rows, which
    would read interpreter overhead as kernel slowness.
    """
    if not bench:
        return "_BENCH_decode.json not present — run `python benchmarks/run.py --smoke`_"
    rows = bench.get("rows", {})
    kernels = {}
    for name, val in rows.items():
        if "/" in name:          # policies/… and roofline/… rows live elsewhere
            continue
        if name.endswith("_interp"):
            kernels.setdefault(name[:-len("_pallas_interp")], {})["interp"] = val
        elif name.endswith("_ref"):
            kernels.setdefault(name[:-len("_ref")], {})["ref"] = val
        elif name.endswith("_pallas"):
            kernels.setdefault(name[:-len("_pallas")], {})["compiled"] = val
    if not kernels:
        return "_no kernel rows in BENCH_decode.json_"
    out = ["| kernel | jnp oracle (µs) | Pallas compiled (µs) | "
           "Pallas interpret (µs) |",
           "|---|---|---|---|"]
    fmt = lambda v: f"{float(v):.0f}" if v is not None else "—"  # noqa: E731
    for name in sorted(kernels):
        r = kernels[name]
        out.append(f"| {name} | {fmt(r.get('ref'))} | "
                   f"{fmt(r.get('compiled'))} | {fmt(r.get('interp'))} |")
    out.append("")
    out.append("Interpret-mode timings are correctness-run costs on CPU, "
               "not kernel performance — compare kernels on the compiled "
               "column (TPU) or via the roofline estimates only.")
    est = {k.split("/")[-1]: v for k, v in rows.items()
           if k.startswith("roofline/fused_verify/")}
    if est:
        out.append("")
        out.append(f"Fused-verify analytic roofline (b=64, k=8, V=32768): "
                   f"{float(est['bytes']) / 2**20:.1f} MiB streamed once, "
                   f"{float(est['flops']) / 1e6:.1f} MFLOP "
                   f"({est['flops_per_byte']} FLOP/B) — memory-bound; v5e "
                   f"floor ≈ {est['v5e_memory_us']} µs.")
    return "\n".join(out)


def dryrun_md(recs) -> str:
    if not recs:
        return "_no dry-run records yet_"
    ok = [r for r in recs if r["status"] == "ok"]
    skipped = [r for r in recs if r["status"] == "skipped"]
    err = [r for r in recs if r["status"] not in ("ok", "skipped")]
    meshes = sorted({r["mesh"] for r in recs})
    out = [f"Records: **{len(ok)} compiled OK**, {len(skipped)} skipped "
           f"(documented), {len(err)} errors, over meshes {meshes}.", ""]
    out.append("| arch | shape | mesh | per-device args | per-device temp | "
               "compile s | collectives |")
    out.append("|---|---|---|---|---|---|---|")
    for r in ok:
        ma = r["memory_analysis"]
        coll = r["collectives"]
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
            f"{(ma['argument_size_bytes'] or 0) / 2**30:.2f} GiB | "
            f"{(ma['temp_size_bytes'] or 0) / 2**30:.2f} GiB | "
            f"{r['compile_s']:.0f} | "
            f"{coll['total_bytes'] / 2**20:.1f} MiB "
            f"{dict(coll['counts'])} |")
    for r in skipped:
        out.append(f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
                   f"SKIPPED: {r['reason']} | | | |")
    for r in err:
        out.append(f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
                   f"ERROR: {r.get('error', '')[:80]} | | | |")
    return "\n".join(out)


def _lever(r) -> str:
    """One sentence: what would move the dominant term down (per brief)."""
    dom = r["roofline"]["bottleneck"]
    kind = r.get("kind", "")
    if dom == "collective_s":
        return ("overlap the expert all-to-all with the shared-expert matmul"
                if "moe" in r["arch"] else
                "reduce-scatter/all-gather sequence-parallel activations")
    if dom == "compute_s":
        return "MXU-aligned block shapes; drop remat recompute"
    if kind == "decode":
        return ("int8 KV cache halves the dominant cache read; larger k "
                "amortizes it over more accepted tokens")
    if kind == "prefill":
        return ("Pallas flash attention keeps score tiles in VMEM "
                "(kernels/block_attention pattern at Sq=block)")
    return ("microbatch + remat bounds activation traffic; "
            "sequence-parallel norms")


def roofline_md(recs) -> str:
    ok = [r for r in recs if r["status"] == "ok" and r["mesh"] == "pod16x16"]
    if not ok:
        return "_no single-pod records yet_"
    out = ["| arch | shape | compute | memory | collective | dominant | "
           "useful FLOPs ratio | lever on the dominant term |",
           "|---|---|---|---|---|---|---|---|"]
    for r in ok:
        t = r["roofline"]
        dom = t["bottleneck"].replace("_s", "")
        note = _lever(r)
        if r.get("sliding_window") and r["shape"] == "long_500k":
            note = f"(SWA {r['sliding_window']}) " + note
        ratio = r.get("useful_flops_ratio")
        out.append(
            f"| {r['arch']} | {r['shape']} | {t['compute_s'] * 1e3:.2f} ms | "
            f"{t['memory_s'] * 1e3:.2f} ms | {t['collective_s'] * 1e3:.2f} ms "
            f"| **{dom}** | {ratio:.3f} | {note} |" if ratio is not None else
            f"| {r['arch']} | {r['shape']} | - | - | - | - | - | {note} |")
    doms = {}
    for r in ok:
        d = r["roofline"]["bottleneck"]
        doms[d] = doms.get(d, 0) + 1
    out.append("")
    out.append(f"Dominant-term distribution (single-pod): {doms}.  The "
               "memory term uses the CPU backend's `bytes accessed` and is "
               "an upper bound (TPU fuses more aggressively); compute and "
               "collective terms are structural.")
    out.append("")
    out.append("All MoE rows (qwen2 / olmoe, both meshes) use the OPTIMIZED "
               "grouped expert dispatch; the pre-optimization baselines "
               "(99.5% more FLOPs, 66× the collective bytes at prefill_32k) "
               "are preserved in experiments/dryrun_moe_baseline/ and "
               "analysed in §Perf #3.")
    return "\n".join(out)


def main():
    t1 = _load("experiments/table1.json")
    t2 = _load("experiments/table2.json")
    t4 = _load("experiments/table4.json")
    bench = _load("BENCH_decode.json")
    recs = load_records("experiments/dryrun")

    if not os.path.exists(EXP):
        print(f"[report] {EXP} not present — printing the KERNELS "
              f"section instead of patching markers")
        print(kernels_md(bench))
        return
    with open(EXP) as f:
        text = f.read()
    for marker, content in (
        ("TABLE1", table1_md(t1)),
        ("TABLE2", table2_md(t2)),
        ("TABLE4", table4_md(t4)),
        ("CLAIMS", claims_md(t1, t2, t4)),
        ("KERNELS", kernels_md(bench)),
        ("DRYRUN", dryrun_md(recs)),
        ("ROOFLINE", roofline_md(recs)),
    ):
        pat = re.compile(rf"<!-- {marker} -->.*?(?=\n## |\n### |\n---|\Z)",
                         re.S)
        if f"<!-- {marker} -->" in text:
            text = pat.sub(f"<!-- {marker} -->\n{content}\n", text)
    with open(EXP, "w") as f:
        f.write(text)
    print(f"[report] EXPERIMENTS.md updated "
          f"(t1={'y' if t1 else 'n'} t2={'y' if t2 else 'n'} "
          f"t4={'y' if t4 else 'n'} dryrun={len(recs)})")


if __name__ == "__main__":
    main()
