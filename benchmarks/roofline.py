"""Roofline analysis from the dry-run artifacts (EXPERIMENTS.md §Roofline).

Hardware model: the published peaks of the chip a record ran on (or, for
dry-run records, was compiled for), looked up by ``device_kind`` in
``PEAKS``.  A device missing from the table is an error, never a
default.  TPU v5e: 197 TFLOP/s bf16/chip, 819 GB/s HBM/chip, ~50
GB/s/link ICI.

All dry-run quantities are per-device (the compiled SPMD module is the
per-device program; probe totals reconstruct while-loop trip counts), so

    compute term    = flops_dev / 197e12
    memory term     = bytes_dev / 819e9
    collective term = coll_bytes_dev / 50e9

MODEL_FLOPS uses 6*N*D for training (N = params, dense; N_active for
MoE) and 2*N*D for single-token decode / prefill forward passes.
"""
from __future__ import annotations

import glob
import json
import os
import sys
from typing import Dict, List, Optional

#: Published per-chip peaks, keyed by ``jax.Device.device_kind``.
PEAKS: Dict[str, Dict] = {
    "TPU v5 lite": dict(
        flops=197e12, hbm_bw=819e9, link_bw=50e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI over 4 links"),
}
#: The chip the dry-run records were compiled for.
DRYRUN_KIND = "TPU v5 lite"


def chip_peaks(device_kind: str) -> Dict:
    """Peaks of ``device_kind``; raises for a chip not in ``PEAKS``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add "
            f"them to benchmarks.roofline.PEAKS with their source") from None


SHAPE_TOKENS = {
    "train_4k": 4096 * 256,
    "prefill_32k": 32768 * 32,
    "decode_32k": 128,          # one new token per sequence
    "long_500k": 1,
}


def model_flops(rec: Dict) -> float:
    n = rec.get("active_params_estimate") or rec.get("params_estimate")
    tokens = SHAPE_TOKENS[rec["shape"]]
    mult = 6.0 if rec["shape"].startswith("train") else 2.0
    return mult * n * tokens


def analyze_record(rec: Dict) -> Optional[Dict]:
    if rec.get("status") != "ok":
        return None
    probe = rec.get("probe")
    if probe:
        flops_dev = probe["flops_total"]
        bytes_dev = probe["bytes_total"]
        coll_dev = probe["coll_bytes_total"]
    else:
        # multi-pod records have no probe; raw values undercount loops
        flops_dev = rec["flops"]
        bytes_dev = rec["bytes_accessed"]
        coll_dev = rec["collective_bytes_total"]
    n_dev = rec["n_devices"]
    peaks = chip_peaks(DRYRUN_KIND)
    t_compute = flops_dev / peaks["flops"]
    t_memory = bytes_dev / peaks["hbm_bw"]
    t_coll = coll_dev / peaks["link_bw"]
    terms = {"compute": t_compute, "memory": t_memory,
             "collective": t_coll}
    dominant = max(terms, key=terms.get)
    mf = model_flops(rec) / n_dev          # per-device useful flops
    useful_ratio = mf / flops_dev if flops_dev else 0.0
    # roofline fraction: useful work at peak vs modeled step time
    step_time = max(terms.values())
    roofline_frac = ((mf / peaks["flops"]) / step_time
                     if step_time else 0.0)
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "compute_s": t_compute, "memory_s": t_memory,
        "collective_s": t_coll, "dominant": dominant,
        "model_flops_dev": mf, "hlo_flops_dev": flops_dev,
        "useful_ratio": useful_ratio, "roofline_frac": roofline_frac,
        "probe": bool(probe),
    }


def suggestion(row: Dict) -> str:
    d = row["dominant"]
    if d == "compute":
        if row["useful_ratio"] < 0.5:
            return ("compute-bound with low useful ratio: cut remat "
                    "recompute / padding waste (head-count or vocab "
                    "padding) before anything else")
        return "compute-bound and efficient: only larger chips help"
    if d == "memory":
        return ("memory-bound: fuse/batch HBM traffic — bigger decode "
                "batch per chip, bf16/int8 KV cache, flash attention")
    return ("collective-bound: overlap grad all-reduce with backprop, "
            "compress cross-pod gradients, or widen TP within pod")


def default_dir() -> str:
    """Latest sweep wins: v3 (optimized round 2) > v2 > v1 baseline."""
    for d in ("results/dryrun_v3", "results/dryrun_v2", "results/dryrun"):
        if os.path.isdir(d) and glob.glob(os.path.join(d, "*.json")):
            return d
    return "results/dryrun"


def load_rows(out_dir: Optional[str] = None) -> List[Dict]:
    """Analyzed rows for every readable record in ``out_dir``.  A
    malformed / truncated / schema-incomplete JSON file (a dry-run
    killed mid-write, a partial artifact download) is *skipped with a
    warning* instead of failing the whole report — one bad record must
    not take down the table the good ones render."""
    out_dir = out_dir or default_dir()
    rows = []
    for path in sorted(glob.glob(os.path.join(out_dir, "*.json"))):
        try:
            with open(path) as f:
                rec = json.load(f)
            row = analyze_record(rec)
        except (json.JSONDecodeError, KeyError, TypeError,
                ValueError, OSError) as exc:
            print(f"roofline: skipping {path}: "
                  f"{type(exc).__name__}: {exc}", file=sys.stderr)
            continue
        if row:
            rows.append(row)
    return rows


def run(out_dir: Optional[str] = None, verbose: bool = True):
    out_dir = out_dir or default_dir()
    rows = load_rows(out_dir)
    if verbose:
        for r in rows:
            if r["mesh"] != "single":
                continue
            print(f"roofline_{r['arch']}_{r['shape']},0.0,"
                  f"compute_s={r['compute_s']:.3e};"
                  f"memory_s={r['memory_s']:.3e};"
                  f"collective_s={r['collective_s']:.3e};"
                  f"dominant={r['dominant']};"
                  f"useful_ratio={r['useful_ratio']:.2f};"
                  f"roofline_frac={r['roofline_frac']:.2f}")
    return rows


def markdown_table(rows: List[Dict]) -> str:
    lines = ["| arch | shape | compute s | memory s | collective s | "
             "dominant | MODEL/HLO | roofline frac | next lever |",
             "|---|---|---|---|---|---|---|---|---|"]
    for r in rows:
        if r["mesh"] != "single":
            continue
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.2e} | "
            f"{r['memory_s']:.2e} | {r['collective_s']:.2e} | "
            f"{r['dominant']} | {r['useful_ratio']:.2f} | "
            f"{r['roofline_frac']:.2f} | {suggestion(r)} |")
    return "\n".join(lines)


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dir", default=None,
                    help="dry-run artifact dir (default: latest sweep)")
    ap.add_argument("--out", default=None,
                    help="write the markdown table to this path")
    args = ap.parse_args()
    table = markdown_table(run(args.dir))
    if args.out:
        with open(args.out, "w") as f:
            f.write(table + "\n")
    print(table)
