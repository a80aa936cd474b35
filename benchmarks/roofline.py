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

Megascan records (``kind: "megascan"``, emitted by the serving bench's
one-launch scan arm) get their own model: the question there is not
FLOP efficiency but *dispatch share* — what fraction of the scan path
is per-launch overhead vs streaming/compute.  A per-shard launch
sequence pays ``launches * DISPATCH_S``; the megascan pays it once and
overlaps the HBM->VMEM block copies with MXU scoring (double-buffered
prefetch), so its modeled time is ``DISPATCH_S + max(memory, compute)``
and the dispatch-bound -> bandwidth-bound claim is the rendered
``dominant`` column flipping, not prose.
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


# Per-launch dispatch overhead model: host-side Pallas/XLA launch plus
# the HBM<->VMEM turnaround a fresh kernel pays before its pipeline
# fills.  ~8 us is the conventional small-kernel launch cost on current
# TPU runtimes; the absolute value only scales the dispatch column —
# the per-shard vs megascan *comparison* divides it out.
DISPATCH_S = 8e-6

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


def analyze_megascan(rec: Dict) -> Dict:
    """Roofline row for a megascan record (see kernels/megascan): the
    three terms are dispatch (launches * DISPATCH_S), memory (payload
    bytes streamed through VMEM once per launch set) and compute
    (scoring + one-hot reduction flops).  With double-buffered prefetch
    memory and compute overlap, so the modeled wall is
    ``dispatch + max(memory, compute)`` and ``overlap_ratio`` says how
    much of the smaller stream the prefetch hides.  Peaks are those of
    the record's ``device_kind``."""
    peaks = chip_peaks(rec["device_kind"])
    launches = int(rec.get("launches", 1))
    t_dispatch = launches * DISPATCH_S
    t_memory = float(rec.get("bytes_streamed", 0)) / peaks["hbm_bw"]
    t_compute = float(rec.get("flops", 0)) / peaks["flops"]
    terms = {"dispatch": t_dispatch, "memory": t_memory,
             "compute": t_compute}
    dominant = max(terms, key=terms.get)
    stream = max(t_memory, t_compute)
    overlap = (min(t_memory, t_compute) / stream) if stream else 0.0
    modeled = t_dispatch + stream
    dispatch_share = t_dispatch / modeled if modeled else 0.0
    return {
        "kind": "megascan",
        "name": rec.get("name", f"megascan_x{launches}"),
        "launches": launches,
        "shards": int(rec.get("shards", 0)),
        "shards_per_launch": (rec.get("shards", 0) / launches
                              if launches else 0.0),
        "dispatch_s": t_dispatch, "memory_s": t_memory,
        "compute_s": t_compute, "dominant": dominant,
        "overlap_ratio": overlap, "modeled_s": modeled,
        "dispatch_share": dispatch_share,
        "bytes_streamed": int(rec.get("bytes_streamed", 0)),
        "measured_wall_s": rec.get("measured_wall_s"),
        "double_buffer": bool(rec.get("double_buffer", False)),
        "device_kind": rec["device_kind"],
    }


def analyze_record(rec: Dict) -> Optional[Dict]:
    if rec.get("kind") == "megascan":
        return analyze_megascan(rec)
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
    if row.get("kind") == "megascan":
        d = row["dominant"]
        if d == "dispatch":
            return ("dispatch-bound: fuse more shards per launch "
                    "(megakernel route) — per-launch overhead dwarfs "
                    "the streamed payload")
        if d == "memory":
            if row["overlap_ratio"] < 0.5:
                return ("bandwidth-bound with idle MXU: raise bits or "
                        "batch more queries per launch to fill the "
                        "prefetch window")
            return ("bandwidth-bound and overlapped: the scan streams "
                    "at HBM speed — only narrower signatures (fewer "
                    "bits) or more chips help")
        return ("compute-bound: the one-hot reduction dominates — "
                "shrink the lane-padded slot axis or lower scoring "
                "precision")
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
            if r.get("kind") == "megascan" or r["mesh"] != "single":
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
        if r.get("kind") == "megascan" or r["mesh"] != "single":
            continue
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.2e} | "
            f"{r['memory_s']:.2e} | {r['collective_s']:.2e} | "
            f"{r['dominant']} | {r['useful_ratio']:.2f} | "
            f"{r['roofline_frac']:.2f} | {suggestion(r)} |")
    return "\n".join(lines)


def megascan_table(rows: List[Dict]) -> str:
    """The scan-path roofline: one row per megascan record, the
    dispatch-share column carrying the dispatch-bound vs
    bandwidth-bound claim."""
    lines = ["| scan | launches | shards/launch | dispatch s | "
             "memory s | compute s | dominant | dispatch share | "
             "overlap | measured s | next lever |",
             "|---|---|---|---|---|---|---|---|---|---|---|"]
    for r in rows:
        if r.get("kind") != "megascan":
            continue
        meas = (f"{r['measured_wall_s']:.2e}"
                if r.get("measured_wall_s") is not None else "-")
        lines.append(
            f"| {r['name']} | {r['launches']} | "
            f"{r['shards_per_launch']:.1f} | {r['dispatch_s']:.2e} | "
            f"{r['memory_s']:.2e} | {r['compute_s']:.2e} | "
            f"{r['dominant']} | {r['dispatch_share']:.2f} | "
            f"{r['overlap_ratio']:.2f} | {meas} | {suggestion(r)} |")
    return "\n".join(lines)


def serve_megascan_rows(serve_json: str) -> List[Dict]:
    """Analyzed megascan rows from a serve-bench report JSON (the
    ``megascan`` record's ``roofline_records`` list)."""
    with open(serve_json) as f:
        report = json.load(f)
    recs = (report.get("megascan") or {}).get("roofline_records", [])
    return [analyze_megascan(r) for r in recs]


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dir", default=None,
                    help="dry-run artifact dir (default: latest sweep)")
    ap.add_argument("--serve", default=None,
                    help="serve-bench JSON: render its megascan records"
                         " instead of the dry-run artifacts")
    ap.add_argument("--out", default=None,
                    help="write the markdown table(s) to this path")
    args = ap.parse_args()
    if args.serve:
        rows = serve_megascan_rows(args.serve)
        table = megascan_table(rows)
    else:
        rows = run(args.dir)
        table = markdown_table(rows)
        mega = megascan_table(rows)
        if mega.count("\n") > 1:
            table = table + "\n\n" + mega
    if args.out:
        with open(args.out, "w") as f:
            f.write(table + "\n")
    print(table)
