"""Generate EXPERIMENTS.md sections from dry-run/benchmark artifacts.

    PYTHONPATH=src python -m benchmarks.report [--v1 results/dryrun]
        [--v2 results/dryrun_v2] [--serve BENCH_serve.json]
        [--out EXPERIMENTS.md]

The perf story is v1 (baseline) -> v2 (optimized): both sweeps are kept
so every before/after claim in §Perf is reproducible from artifacts.
``--serve`` additionally renders the serving benchmark (BENCH_serve.json
from benchmarks/serve_bench.py) — the execution-mode throughput table
plus, when present, the ``load_sweep`` (static vs adaptive window
sojourn across arrival rates), ``placement`` (simulated multi-host
topology: residency split, gather parity, relative throughput) and
``balance`` (replica-aware hot-host balancing: primary vs balanced
makespan, estimated vs realized per-host walls, shed counts) and
``chaos`` (the elastic-fleet scenario: scripted kill/join/drain phase
makespans, parity and zero-loss gates, membership audit) records, and
the speedup scalars.  A record kind this report has no renderer
for prints a one-line shape summary instead of vanishing — earlier
report versions silently dropped unknown kinds.
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


def _summarize_record(value) -> str:
    """One-line shape summary for a record kind this report has no
    renderer for — unknown kinds must never vanish silently."""
    if isinstance(value, dict):
        keys = ", ".join(list(value)[:6])
        more = ", …" if len(value) > 6 else ""
        return f"dict with keys {keys}{more}"
    if isinstance(value, (list, tuple)):
        return f"list of {len(value)} entries"
    return repr(value)


def serve_section(serve: Dict) -> str:
    """§Serving from a BENCH_serve.json: execution-mode table +
    load_sweep / placement / balance records + speedup scalars; any
    record kind without a renderer still prints a one-line summary
    (nothing in the JSON is dropped on the floor)."""
    rendered = {"config", "load_sweep", "placement", "balance", "budget",
                "chaos", "cache", "ingest"}
    lines = ["## §Serving", ""]
    cfg = serve.get("config", {})
    if cfg:
        lines += [f"{cfg.get('n_queries', '?')} mixed queries "
                  f"(agg/bool/ranked) at rate {cfg.get('rate', '?')}, "
                  f"batch {cfg.get('batch_size', '?')}, "
                  f"{cfg.get('n_shards', '?')} shards"
                  + (", smoke corpus" if cfg.get("smoke") else ""), ""]
    lines += ["| mode | q/s | p50 ms |", "|---|---|---|"]
    for mode, rec in serve.items():
        if not (isinstance(rec, dict) and "qps" in rec):
            continue
        rendered.add(mode)
        p50 = rec.get("p50_ms", rec.get("p50_sojourn_ms"))
        p50s = f"{p50:.2f}" if p50 is not None else "—"
        note = " (sojourn)" if "p50_sojourn_ms" in rec else ""
        lines.append(f"| {mode} | {rec['qps']:.0f} | {p50s}{note} |")
    lines.append("")
    speedups = [(k, v) for k, v in serve.items()
                if k.startswith("speedup_") and isinstance(v, (int, float))]
    if speedups:
        rendered.update(k for k, _ in speedups)
        lines += ["Speedups: " + ", ".join(
            f"{k[len('speedup_'):].replace('_', ' ')} **{v:.2f}x**"
            for k, v in speedups), ""]

    sweep = serve.get("load_sweep")
    if sweep:
        lines += ["### Load sweep (static vs adaptive vs budget window)",
                  "",
                  "| load | mode | target q/s | served q/s | p50 ms | "
                  "p99 ms | mean batch | shed | degraded | p90 rel err |",
                  "|---|---|---|---|---|---|---|---|---|---|"]
        def _pct(v):
            return f"{v:.0%}" if isinstance(v, (int, float)) else "—"

        def _err(v):
            # NaN (no count queries served) renders as a dash
            return f"{v:.2f}" if isinstance(v, (int, float)) and v == v \
                else "—"

        for row in sweep:
            lines.append(
                f"| {row['load']} | {row['mode']} | "
                f"{row['arrival_qps_target']:.0f} | "
                f"{row['served_qps']:.0f} | "
                f"{row['p50_sojourn_ms']:.2f} | "
                f"{row['p99_sojourn_ms']:.2f} | "
                f"{row['mean_batch']:.1f} | "
                f"{_pct(row.get('shed_frac'))} | "
                f"{_pct(row.get('degraded_frac'))} | "
                f"{_err(row.get('p90_rel_err'))} |")
        lines.append("")

    pl = serve.get("placement")
    if pl:
        parity = pl.get("parity", {})
        lines += [
            f"### Placement ({pl.get('hosts', '?')} hosts, "
            f"{pl.get('policy', '?')}, {pl.get('n_replicas', 0)} replica)",
            "",
            f"- per-host scans {pl.get('scans_per_host')} vs union-plan "
            f"residency split {pl.get('expected_scans_per_host')} — "
            f"match: **{pl.get('residency_match')}**",
            "- cross-host gather parity vs single executor: "
            + ", ".join(f"{k}={v}" for k, v in parity.items()),
            f"- throughput vs single-host: "
            f"**{pl.get('qps_ratio_vs_single_host', float('nan')):.2f}x**",
            "",
        ]

    bal = serve.get("balance")
    if bal:
        audit = bal.get("last_audit") or {}
        est = audit.get("est_cost_s") or []
        walls = audit.get("realized_wall_s") or []
        parity = bal.get("parity", {})
        lines += [
            f"### Replica-aware balance ({bal.get('hosts', '?')} hosts, "
            f"{bal.get('n_replicas', 0)} replica, host "
            f"{bal.get('hot_host', '?')} degraded "
            f"{bal.get('hot_delay_ms_per_shard', 0):.1f} ms/shard)",
            "",
            f"- mean job makespan: primary-only "
            f"{bal.get('primary_mean_makespan_ms', float('nan')):.2f} ms "
            f"-> balanced "
            f"{bal.get('balanced_mean_makespan_ms', float('nan')):.2f} ms "
            f"(**{bal.get('makespan_reduction', float('nan')):.2f}x** "
            f"down; {bal.get('shed_shards', 0)} shard scans shed to "
            f"replicas)",
        ]
        sizes = audit.get("group_sizes") or []
        if est and walls and sizes:
            per_host = ", ".join(
                f"h{h} est {1e3 * (c or 0) * n:.2f}/realized "
                f"{1e3 * w:.2f}"
                for h, (c, w, n) in enumerate(zip(est, walls, sizes)))
            lines.append(
                f"- last job per-host wall ms (est = cost x group vs "
                f"realized): {per_host}; split {sizes} vs "
                f"residency {audit.get('base_group_sizes')}")
        lines += [
            "- gather parity (vs single executor): "
            + ", ".join(f"{k}={v}" for k, v in parity.items())
            + "; vs primary-only split: "
            + ", ".join(f"{k}={v}"
                        for k, v in bal.get("parity_vs_primary",
                                            {}).items()),
            "",
        ]

    bud = serve.get("budget")
    if bud:
        cov = bud.get("coverage", {})
        parity = bud.get("parity", {})
        lines += [
            f"### Error-budgeted serving ({bud.get('hosts', '?')} hosts, "
            f"host {bud.get('hot_host', '?')} degraded "
            f"{bud.get('hot_delay_ms_per_shard', 0):.1f} ms/shard, "
            f"capacity {bud.get('capacity_qps', float('nan')):.0f} q/s)",
            "",
            "- planner parity (budget-free queries, planner engine vs "
            "plain): " + "; ".join(
                f"{lbl}: " + ", ".join(f"{k}={v}" for k, v in p.items())
                for lbl, p in parity.items()),
        ]
        for lbl, c in cov.items():
            lines.append(
                f"- {lbl} pass: count 95% CI coverage "
                f"**{c.get('ci_coverage', float('nan')):.0%}** over "
                f"{c.get('n_count_queries', '?')} queries, p90 realized "
                f"rel err {c.get('p90_rel_err', float('nan')):.2f}")
        for lbl in ("planned", "degraded"):
            a = bud.get(f"{lbl}_audit") or {}
            if a:
                lines.append(
                    f"- {lbl} audit: pressure {a.get('pressure', 0):.2f}, "
                    f"{a.get('degraded', 0)}/{a.get('budgeted', 0)} "
                    f"queries degraded, {a.get('at_floor', 0)} at floor")
        ov = bud.get("overload", {})
        if ov:
            lines += ["", "| overload arm | offered q/s | served q/s | "
                      "shed | degraded | mean batch | p99 ms | "
                      "CI coverage |",
                      "|---|---|---|---|---|---|---|---|"]
            for mode, arm in ov.items():
                covs = arm.get("ci_coverage")
                lines.append(
                    f"| {mode} | {arm['offered_qps']:.0f} | "
                    f"{arm['served_qps']:.0f} | "
                    f"{arm['shed']}/{arm['shed'] + arm['served']} | "
                    f"{arm['degraded_frac']:.0%} | "
                    f"{arm['mean_batch']:.1f} | "
                    f"{arm['p99_sojourn_ms']:.0f} | "
                    + (f"{covs:.0%} |" if isinstance(covs, (int, float))
                       and covs == covs else "— |"))
        lines.append("")

    ch = serve.get("chaos")
    if ch:
        parity = ch.get("parity", {})
        fleet = ch.get("fleet", {})
        fired = (ch.get("faults") or {}).get("fired", {})
        lines += [
            f"### Elastic-fleet chaos ({ch.get('hosts', '?')} hosts, "
            f"{ch.get('n_replicas', 0)} replica, every host slowed "
            f"{ch.get('slow_ms_per_shard', 0):.1f} ms/shard)",
            "",
            "Scripted kill -> serve-degraded -> join -> recover -> "
            "drain scenario (seeded FaultPlan through FleetManager; "
            "every gate below is a hard failure in CI):",
            "",
            "| phase | makespan ms |", "|---|---|"]
        for phase, ms in (ch.get("phase_makespan_ms") or {}).items():
            lines.append(f"| {phase} | {ms:.1f} |")
        lines += [
            "",
            f"- lost queries **{ch.get('lost_queries', '?')}**, lost "
            f"shards **{ch.get('lost_shards', '?')}** (floor: zero — "
            f"one replica survives every scripted failure)",
            "- gather parity vs single executor, all phases (kill "
            "batch included): "
            + ", ".join(f"{k}={v}" for k, v in parity.items()),
            f"- kill landed: degraded makespan "
            f"**{ch.get('degradation_ratio', float('nan')):.2f}x** "
            f"healthy (floor 1.3x); post-join recovery "
            f"**{ch.get('recovery_ratio', float('nan')):.2f}x** healthy "
            f"(ceiling 1.25x)",
            f"- joiner warmed **{ch.get('warmed_shards', '?')}** shards "
            f"before residency; drain moved "
            f"{(ch.get('drain') or {}).get('moved_shards', '?')} shards, "
            f"orphaned "
            f"{(ch.get('drain') or {}).get('orphaned_shards', '?')}",
            f"- membership: {fleet.get('joins', 0)} join / "
            f"{fleet.get('drains', 0)} drain / "
            f"{fleet.get('crashes', 0)} crash, "
            f"{fleet.get('placement_epoch', 0)} placement generations, "
            f"live hosts {fleet.get('live_hosts')}",
            "- faults fired (scenario): "
            + ", ".join(f"{k}={v}" for k, v in fired.items()),
            "",
        ]

    ca = serve.get("cache")
    if ca:
        z = ca.get("zipf") or {}
        zstats = z.get("stats") or {}
        sh = ca.get("single_host") or {}
        fl = ca.get("fleet") or {}
        cold = sh.get("cold_parity", {})
        warm = sh.get("warm_parity", {})
        lines += [
            "### Semantic query cache (LSH-signature keyed)",
            "",
            f"Zipf stream (skew {z.get('skew', '?')}): "
            f"{z.get('stream', '?')} queries over a "
            f"{z.get('pool', '?')}-query pool — cached p50 "
            f"**{z.get('cached_p50_ms', float('nan')):.3f} ms** vs "
            f"uncached {z.get('uncached_p50_ms', float('nan')):.3f} ms "
            f"(**{z.get('p50_collapse', float('nan')):.1f}x** collapse; "
            f"gate: cached must be strictly below), "
            f"{zstats.get('hits', '?')} hits / "
            f"{zstats.get('near_hits', '?')} near / "
            f"{zstats.get('misses', '?')} misses",
            "",
            "- exact-hit parity (radius 0): cold pass bit-for-bit the "
            "uncached engine "
            + ", ".join(f"{k}={v}" for k, v in cold.items())
            + "; warm pass all "
            f"{(sh.get('stats') or {}).get('hits', '?')} hits "
            "bit-for-bit the cold results "
            + ", ".join(f"{k}={v}" for k, v in warm.items()),
            f"- generation fencing ({fl.get('hosts', '?')} hosts): "
            f"join dropped "
            f"{(fl.get('join') or {}).get('stale_dropped', '?')} stale "
            f"entries, drain dropped "
            f"{(fl.get('drain') or {}).get('stale_dropped', '?')} — "
            f"zero cache hits crossed either swap (hard gate)",
            "",
        ]

    ing = serve.get("ingest")
    if ing:
        sw = ing.get("swap") or {}
        la = ing.get("latency") or {}
        cf = ing.get("cache_fence") or {}
        fr = sw.get("freshness") or {}
        gen = sw.get("generation") or {}
        tr = ing.get("timed_row") or {}
        lines += [
            "### Live ingest (append -> generation -> fence)",
            "",
            f"Mid-run append of **{sw.get('n_new', '?')}** sentinel "
            f"docs ({100 * ing.get('fraction', 0):.0f}% of the corpus) "
            f"racing the serving loop — "
            f"{sw.get('served_during_swap', '?')} batches served "
            f"during the swap ({sw.get('old_generation_batches', '?')} "
            f"old-generation, {sw.get('new_generation_batches', '?')} "
            f"new), every one bit-for-bit one of the two reference "
            f"worlds (hard gate: no torn reads, zero loss)",
            "",
            f"- freshness: sentinel-phrase count "
            f"{fr.get('before', '?'):.0f} -> "
            f"**{fr.get('after', '?'):.0f}** at error bound 0 after "
            f"the swap; generation "
            f"(placement={gen.get('placement', '?')}, "
            f"content={gen.get('content', '?')})",
            f"- zero pause: serving p99 with the paced writer racing "
            f"**{la.get('ingest_p99_ms', float('nan')):.3f} ms** vs "
            f"{la.get('no_ingest_p99_ms', float('nan')):.3f} ms "
            f"no-ingest — **{la.get('ratio', float('nan')):.2f}x** "
            f"(hard gate: <= {la.get('bound', '?')}x, "
            f"{la.get('passes', '?')} pool passes per trial)",
            f"- content-axis cache fence: "
            f"{cf.get('stale_dropped', '?')}/{cf.get('pool', '?')} "
            f"warm entries dropped as stale across the step, zero "
            f"stale hits, post-ingest re-serve bit-for-bit a plain "
            f"engine on the appended world (hard gate)",
            f"- timed arm: {tr.get('steps', '?')} steps, "
            f"{tr.get('docs_appended', '?')} docs appended, "
            f"{tr.get('swaps', '?')} swaps, "
            f"{tr.get('shards_added', '?')} shards added",
            "",
        ]

    mg = serve.get("megascan")
    if mg:
        from benchmarks.roofline import analyze_megascan, megascan_table
        rendered.add("megascan")
        meas = mg.get("measured") or {}
        ds = mg.get("dispatch_share")
        ds = ds if isinstance(ds, dict) else {}     # "not measured" off-chip
        launches = mg.get("launches") or {}
        jobs = mg.get("host_megascan_jobs")
        lines += [
            "### One-launch scan-over-shards megakernel",
            "",
            f"{mg.get('queries', '?')} full-fleet similarity scans over "
            f"{mg.get('shards', '?')} shards: "
            f"**{launches.get('mega', '?')}** launch vs "
            f"{launches.get('per_shard', '?')} per-shard launches — "
            f"measured **{meas.get('win', float('nan')):.2f}x** faster "
            f"({meas.get('mega_s', float('nan')):.4f}s vs "
            f"{meas.get('per_shard_s', float('nan')):.4f}s, hard gate: "
            f"one-launch must win), dispatch share "
            f"{ds.get('per_shard', float('nan')):.2f} -> "
            f"**{ds.get('mega', float('nan')):.2f}** (hard gate: must "
            f"drop)",
            "",
            "- group-vs-per-shard gather parity on ragged plans "
            "(bit-for-bit, hard gate): "
            + ", ".join(f"{k}={v}"
                        for k, v in (mg.get("parity") or {}).items())
            + (f"; host-group parity={mg['host_group_parity']}"
               if "host_group_parity" in mg else "")
            + (f", per-host launches {jobs}" if jobs else ""),
            "",
            megascan_table([analyze_megascan(r) for r in
                            mg.get("roofline_records", [])]),
            "",
        ]

    unknown = [k for k in serve if k not in rendered]
    for k in unknown:
        lines.append(f"- unrecognized record `{k}`: "
                     f"{_summarize_record(serve[k])}")
    if unknown:
        lines.append("")
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--v1", default="results/dryrun")
    ap.add_argument("--v2", default=None,
                    help="optimized sweep dir (default: latest)")
    ap.add_argument("--serve", default="BENCH_serve.json",
                    help="serving bench JSON (skipped when absent)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    from benchmarks.roofline import default_dir
    v2_dir = args.v2 or default_dir()
    v1 = load_recs(args.v1)
    v2 = load_recs(v2_dir) if os.path.isdir(v2_dir) else v1
    text = dryrun_section(v2) + "\n" + roofline_section(v2) + "\n" + \
        perf_compare_section(v1, v2)
    if args.serve and os.path.exists(args.serve):
        text += "\n" + serve_section(json.load(open(args.serve)))
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    else:
        print(text)


if __name__ == "__main__":
    main()
