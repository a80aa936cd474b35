"""Generate EXPERIMENTS.md sections from the dry-run artifacts.

    PYTHONPATH=src python -m benchmarks.report [--v1 results/dryrun]
        [--v2 results/dryrun_v2] [--out EXPERIMENTS.md]

The perf story is v1 (baseline) -> v2 (optimized): both sweeps are kept
so every before/after claim in §Perf is reproducible from artifacts.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
from typing import Dict

from benchmarks.roofline import (
    DRYRUN_KIND,
    analyze_record,
    chip_peaks,
    markdown_table,
)


def load_recs(d: str) -> Dict[str, Dict]:
    out = {}
    for p in sorted(glob.glob(os.path.join(d, "*.json"))):
        r = json.load(open(p))
        out[f"{r['arch']}|{r['shape']}|{r['mesh']}"] = r
    return out


def mem_gib(rec) -> float:
    m = rec.get("memory", {})
    # donated buffers alias args; live footprint ~ args + temp
    return (m.get("argument_bytes", 0) + m.get("temp_bytes", 0)) / 2 ** 30


def dryrun_section(recs: Dict[str, Dict]) -> str:
    lines = [
        "## §Dry-run",
        "",
        "Single-pod mesh (16,16)=256 chips and multi-pod (2,16,16)=512",
        "chips; every cell is `jit(step).lower(**abstract).compile()` on",
        "512 placeholder host devices — no allocation, shardings fully",
        "validated by the SPMD partitioner.  Per-device live memory =",
        "argument + temp bytes from `compiled.memory_analysis()` (outputs",
        "alias donated inputs).  Budget: 16 GiB (v5e).",
        "",
        "| arch | shape | mesh | status | live GiB | fits | HLO flops/dev (probe) | collective B/dev | compile s |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    n_ok = n_skip = n_over = 0
    for key in sorted(recs):
        r = recs[key]
        if r["status"] == "skipped":
            n_skip += 1
            lines.append(f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
                         f"SKIP (policy) | — | — | — | — | — |")
            continue
        if r["status"] != "ok":
            lines.append(f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
                         f"ERROR | — | — | — | — | — |")
            continue
        n_ok += 1
        g = mem_gib(r)
        fits = "yes" if g <= 16.0 else "NO"
        if g > 16.0:
            n_over += 1
        probe = r.get("probe", {})
        fl = probe.get("flops_total", r.get("flops", 0))
        cb = probe.get("coll_bytes_total", r.get("collective_bytes_total", 0))
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} | ok | "
            f"{g:.1f} | {fits} | {fl:.2e} | {cb:.2e} | "
            f"{r.get('compile_s', 0):.0f} |")
    lines += ["",
              f"**{n_ok} compiled ok, {n_skip} policy skips "
              f"(long_500k x full-attention archs, DESIGN.md §6), "
              f"{n_over} over the 16 GiB budget.**", ""]
    return "\n".join(lines)


def roofline_section(recs: Dict[str, Dict]) -> str:
    rows = [analyze_record(r) for r in recs.values()]
    rows = [r for r in rows if r]
    rows.sort(key=lambda r: (r["arch"], r["shape"]))
    peaks = chip_peaks(DRYRUN_KIND)
    lines = [
        "## §Roofline",
        "",
        f"Terms per device (the compiled SPMD module is the per-device "
        f"program, compiled for {DRYRUN_KIND}): compute = "
        f"HLO_FLOPs/{peaks['flops']:.0e}, memory = "
        f"HLO_bytes/{peaks['hbm_bw']:.0e}, collective = "
        f"coll_bytes/{peaks['link_bw']:.0e}.",
        "HLO totals come from the two-point depth probe (unrolled 1- and",
        "2-layer compiles) because XLA cost_analysis counts while-loop",
        "bodies once.  Notes: (1) the CPU backend's HLO is less fused",
        "than TPU's, so the memory term is an upper bound; (2)",
        "MODEL_FLOPS = 6·N·D (train) / 2·N_active·D (serve).",
        "",
        markdown_table(rows),
        "",
    ]
    return "\n".join(lines)


def perf_compare_section(v1: Dict[str, Dict], v2: Dict[str, Dict]) -> str:
    lines = [
        "### v1 -> v2 per-cell effect (single-pod)",
        "",
        "| arch | shape | live GiB v1 | v2 | coll B v1 | v2 |",
        "|---|---|---|---|---|---|",
    ]
    for key in sorted(v2):
        if not key.endswith("|single"):
            continue
        r2 = v2[key]
        r1 = v1.get(key)
        if not r1 or r1["status"] != "ok" or r2["status"] != "ok":
            continue
        c1 = r1.get("probe", {}).get("coll_bytes_total",
                                     r1.get("collective_bytes_total", 0))
        c2 = r2.get("probe", {}).get("coll_bytes_total",
                                     r2.get("collective_bytes_total", 0))
        lines.append(f"| {r2['arch']} | {r2['shape']} | {mem_gib(r1):.1f} | "
                     f"**{mem_gib(r2):.1f}** | {c1:.2e} | {c2:.2e} |")
    return "\n".join(lines) + "\n"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--v1", default="results/dryrun")
    ap.add_argument("--v2", default=None,
                    help="optimized sweep dir (default: latest)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    from benchmarks.roofline import default_dir
    v2_dir = args.v2 or default_dir()
    v1 = load_recs(args.v1)
    v2 = load_recs(v2_dir) if os.path.isdir(v2_dir) else v1
    text = dryrun_section(v2) + "\n" + roofline_section(v2) + "\n" + \
        perf_compare_section(v1, v2)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    else:
        print(text)


if __name__ == "__main__":
    main()
