"""Run one cell of the benchmark on the chip this process finds.

    python3 bench/run.py --workload wiki-phrase.steady --seed 7 \
        --seconds 20 --trace 0

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a
configuration (``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<traffic>.json``).  The deployment (corpus,
index build) and the set of queries offered come from the
configuration's ``data_seed``; ``--seed`` picks where the window
enters the open loop's cycle of arrivals and queries, or orders the
closed loop's queries, and seeds the sampler and the check, so every
seed does the same work in another order.  The run:

  1. set-up (``setup_s``): the corpus, the program's index build and
     serving stack, the queries, and a warm-up that compiles every
     shape the window can use;
  2. the window: ``--seconds`` of open-loop or closed-loop load through
     ``stack.window.submit``; with ``--trace 1`` under the profiler,
     with host spans around the calls into each layer;
  3. the check: a sample of the answered queries against the plain
     reference (``bench/check.py``), once the program's state is freed.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics), ``device``, with a trace
``breakdown``, and last ``checks``: each number compared with its
limit.  Off a TPU, or with fewer chips than the cell asks for, it exits
2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Any, Callable, Dict, Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

from bench import check, deploy, drive, observe, roofline  # noqa: E402
from bench.deploy import say  # noqa: E402
from bench.gen import queries as gen_queries  # noqa: E402
from bench.reference import Reference, ShardView, phrase_counter  # noqa: E402

SETTLE_S = 60.0


def load_cell(name: str):
    """(BENCHMARK.json, the cell, its configuration, its traffic)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"no workload named {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "bench", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return bench, cell, cfg, traffic


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), holding
    every program however short its compile."""
    import jax
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def percentile(x: np.ndarray, p: float) -> float:
    return float(np.percentile(x, p)) if len(x) else float("nan")


def drawer(dep, ref, traffic: Dict[str, Any], rng: np.random.Generator):
    """``draw(n, exclude)``: ``n`` distinct queries of the traffic's mix
    over the deployment's corpus."""
    counts = np.bincount(dep.corpus.tokens, minlength=dep.corpus.vocab_size)
    needs_phrases = "count" in traffic["mix"] and not traffic[
        "phrase"].get("single_word")
    count_fn = phrase_counter(dep.corpus) if needs_phrases else None
    views = [ShardView(ref, s) for s in range(ref.n_shards)]

    def draw(n, exclude=frozenset()):
        return gen_queries.draw_queries(traffic, n, rng, counts, views,
                                        count_fn, exclude)
    return draw


def run_cell(cfg: Dict[str, Any], traffic: Dict[str, Any], seed: int,
             seconds: float, trace: bool, *, chips: int = 1,
             require_chip: bool = True, e2e=(), per_layer=(),
             control: bool = False,
             hook: Optional[Callable[[Any], None]] = None,
             t_start: float = T_START) -> Optional[Dict[str, Any]]:
    """One run of a cell; returns the result line's object, or None when
    ``require_chip`` and JAX finds no TPU or too few chips.  ``hook``,
    when given, is called with the serving stack before the window (the
    tests plant faults with it).  With ``control`` the bfloat16
    reference takes the planner's place in the comparison, so
    ``correct`` and ``checks`` are the control's, and ``program`` holds
    the program's own."""
    import jax
    enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if require_chip and (dev.platform != "tpu" or len(devices) < chips):
        say(f"JAX found {len(devices)} {dev.platform} device(s); this cell "
            f"needs {chips} TPU chip(s)")
        return None
    compiles = drive.Compiles()
    # the deployment and the set of queries it is offered come from the
    # configuration's data seed, so every run does the same work; the
    # run's seed orders it, and seeds the sampler and the check
    data = deploy.sub_seeds(cfg["data_seed"])
    seeds = deploy.sub_seeds(seed)
    dep = deploy.build(cfg, data, seeds["serve"])
    stack = dep.stack
    ref = Reference(dep.corpus, dep.shard_docs, dep.tables)

    t = time.perf_counter()
    draw = drawer(dep, ref, traffic, np.random.default_rng(data["traffic"]))
    order = np.random.default_rng(seeds["traffic"])
    warm = draw(traffic["warmup_queries"])
    if traffic["loop"] == "open":
        # one cycle of arrivals, each with its query, for every seed:
        # the seed picks where the window enters it
        n = int(round(traffic["rate_qps"] * seconds))
        start = int(order.integers(n))
        due = gen_queries.open_arrivals(
            traffic["rate_qps"], seconds,
            np.random.default_rng(data["traffic"]), start)
        timed = draw(n, set(warm))
        timed = timed[start:] + timed[:start]
    else:
        timed = draw(int(traffic["pool_qps"] * seconds), set(warm))
        timed = [timed[i] for i in order.permutation(len(timed))]
    say(f"set-up queries {time.perf_counter() - t:.3f} s: {len(warm)} "
        f"warm-up, {len(timed)} timed")
    t = time.perf_counter()
    max_words = 3 * stack.config.max_batch if "bool" in traffic["mix"] else 0
    deploy.warm_up(dep, warm, max_words)
    program_q = [deploy.to_program(q) for q in timed]
    say(f"set-up warm-up {time.perf_counter() - t:.3f} s, "
        f"{compiles.n} compiles so far ({compiles.seconds:.3f} s)")
    if hook is not None:
        hook(stack)
    # what set-up made lives as long as the server: keep the collector
    # from walking it again in the window
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start

    # ---- the window ----------------------------------------------
    layers = drive.Layers(stack) if trace else None
    log_dir = None
    if trace:
        from jax import profiler
        log_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = profiler.ProfileOptions()
        opts.python_tracer_level = 0
        profiler.start_trace(log_dir, profiler_options=opts)
    trace_a = time.perf_counter()
    n_compiles, n_hits = compiles.n, compiles.cache_hits
    stats0 = dict(stack.window.stats)
    t0 = time.perf_counter() + 0.05
    t1 = t0 + seconds
    if traffic["loop"] == "open":
        off = drive.open_loop(stack.window, program_q, due, t0)
        time.sleep(max(0.0, t1 - time.perf_counter()))
    else:
        off = drive.closed_loop(stack.window, program_q, traffic["clients"],
                                t0, seconds)
    stats = {k: v - stats0.get(k, 0) for k, v in stack.window.stats.items()}
    in_window_compiles = compiles.n - n_compiles
    in_window_hits = compiles.cache_hits - n_hits
    trace_b = time.perf_counter()
    if trace:
        profiler.stop_trace()
    # the window keeps serving while the trace stops, and the trace
    # holds those calls' kernels: the calls it can hold end here
    trace_c = time.perf_counter()
    off.settle(t1 + SETTLE_S)
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)

    # ---- end-to-end and per-layer readings ------------------------
    if traffic["loop"] == "open":
        window_idx = np.arange(off.used)
    else:
        window_idx = np.nonzero(off.sent[:off.used] < t1)[0]
    answered, failed = [], 0
    for i in window_idx:
        f = off.futs[i]
        if f.done() and not f.cancelled() and f.exception() is None:
            answered.append(int(i))
        else:
            failed += 1
    soj = (off.done - off.due)[answered]
    late = (off.sent - off.due)[:off.used] if traffic["loop"] == "open" \
        else np.zeros(0)
    done_in = np.count_nonzero((off.done >= t0) & (off.done < t1))
    values = dict(p50_ms=1e3 * percentile(soj, 50),
                  qps=done_in / seconds, setup_s=setup_s)
    units = dict(p50_ms="ms", qps="q/s", setup_s="s")
    device = dict(platform=dev.platform, kind=dev.device_kind,
                  count=len(devices), memory_peak_bytes=int(mem))
    diag = dict(sojourn_ms={str(p): 1e3 * percentile(soj, p)
                            for p in (50, 75, 90, 95, 99)},
                in_window_compiles=in_window_compiles,
                in_window_cache_hits=in_window_hits,
                compiles=compiles.n, cache_hits=compiles.cache_hits,
                queries=len(window_idx),
                batches=stats.get("batches", 0),
                served=stats.get("served", 0),
                pool_wrapped=bool(off.used > len(program_q)),
                late_p95_ms=1e3 * percentile(late, 95) if len(late) else 0.0,
                late_max_ms=1e3 * float(late.max()) if len(late) else 0.0,
                set_up=dep.phases)
    metrics: Dict[str, Any] = {}
    breakdown = None
    if trace:
        from bench.trace import Trace, find_trace
        tr = Trace(find_trace(log_dir))
        shutil.rmtree(log_dir, ignore_errors=True)
        sizes = dict(n_docs=ref.n_docs, n_shards=ref.n_shards,
                     bits=dep.tables["bits"],
                     dim=int(dep.tables["word_vecs"].shape[1]))
        obs = observe.Observation(off, layers, stats, t0, t1, sizes,
                                  dev.device_kind, tr, (trace_a, trace_c))
        for m in per_layer:
            v = observe.reader(m["name"])(obs)
            if v is not None:
                metrics[m["name"]] = dict(value=float(v), unit=m["unit"])
        device.update(busy_s=tr.busy_s(), window_s=trace_b - trace_a)
        a, b = tr.span()
        breakdown = dict(device_ops=tr.top_ops(10),
                         idle_gaps=tr.idle_gaps(a, b, 10))
        diag.update(segsum_events=tr.kernel(roofline.KERNELS["segsum"])[0],
                    segsum_calls=len(obs.traced_calls()))
        layers.close()
    else:
        for m in e2e:
            metrics[m["name"]] = dict(value=float(values[m["name"]]),
                                      unit=units[m["name"]])
    say("diagnostics " + json.dumps(diag))

    # ---- the check, with the program's state freed ---------------
    answers = {i: off.futs[i].result() for i in answered}
    offered = [timed[i % len(timed)] for i in range(off.used)]
    chosen = check.pick(offered, answered, traffic["check"],
                        np.random.default_rng(seeds["traffic"] + 1))
    dep.close()
    del dep, stack, off, layers, program_q
    gc.unfreeze()
    gc.collect()
    t = time.perf_counter()

    def judge(control: bool):
        nums = check.compare(ref, [offered[i] for i in chosen],
                             [answers[i] for i in chosen],
                             cfg["serve"]["sampling_rate"],
                             np.random.default_rng(seeds["traffic"] + 2),
                             control=control)
        nums["unanswered"] = failed
        limits = cfg["limits"]
        return (all(nums[k] <= limits[k] for k in check.NUMBERS),
                {k: dict(value=nums[k], limit=limits[k])
                 for k in check.NUMBERS})

    correct, checks = judge(False)
    if control:
        program = dict(correct=correct, checks=checks)
        correct, checks = judge(True)
    say(f"check {time.perf_counter() - t:.3f} s over {len(chosen)} "
        f"queries")
    out = dict(correct=bool(correct), attempted=int(len(window_idx)),
               failed=int(failed), metrics=metrics, device=device)
    if breakdown is not None:
        out["breakdown"] = breakdown
    if control:
        out["program"] = program
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench, cell, cfg, traffic = load_cell(args.workload)

    def listed(m):
        return "workloads" not in m or cell["name"] in m["workloads"]

    out = run_cell(cfg, traffic, args.seed, args.seconds, bool(args.trace),
                   chips=cell["chips"],
                   e2e=[m for m in bench["end_to_end"] if listed(m)],
                   per_layer=[m for m in bench["per_layer"] if listed(m)])
    if out is None:
        return 2
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
