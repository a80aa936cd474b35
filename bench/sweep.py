"""Find a deployment's knee: the highest open-loop rate whose backlog
does not grow over a window.

    python3 bench/sweep.py --workload wiki-phrase.steady --seed 3 \
        --seconds 10 --rates 20,30,40,50,60,80

One process pays the set-up once, then offers the cell's traffic mix at
each rate in turn (fresh queries each time), waits for the backlog to
drain, and prints one JSON line per rate: offered and completed q/s,
p50 and p95 sojourn, and the median sojourn of the last quarter of the
window over that of the first quarter (a backlog that grows makes it
climb).  Run it on the chip; the rate that a traffic file offers is
written into it by hand, with the sweep's output in PERF.md.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

import numpy as np  # noqa: E402

from bench import deploy, drive, run  # noqa: E402
from bench.gen import queries as gen_queries  # noqa: E402
from bench.reference import Reference  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    rates = [float(r) for r in args.rates.split(",")]
    _, cell, cfg, traffic = run.load_cell(args.workload)
    import jax
    run.enable_compile_cache()
    if jax.devices()[0].platform != "tpu":
        deploy.say("the sweep runs only on the chip")
        return 2
    seeds = deploy.sub_seeds(cfg["data_seed"])
    dep = deploy.build(cfg, seeds, deploy.sub_seeds(args.seed)["serve"])
    rng = np.random.default_rng(seeds["traffic"])
    draw = run.drawer(dep, Reference(dep.corpus, dep.shard_docs, dep.tables),
                      traffic, rng)
    warm = draw(traffic["warmup_queries"])
    seen = set(warm)
    plans = []
    for r in rates:
        due = gen_queries.open_arrivals(r, args.seconds, rng)
        qs = draw(len(due), seen)
        seen |= set(qs)
        plans.append((r, due, [deploy.to_program(q) for q in qs]))
    deploy.warm_up(dep, warm, 3 * dep.stack.config.max_batch
                   if "bool" in traffic["mix"] else 0)
    deploy.say(f"sweep set-up {time.perf_counter() - T_START:.3f} s")
    for r, due, qs in plans:
        t0 = time.perf_counter() + 0.05
        off = drive.open_loop(dep.stack.window, qs, due, t0)
        t1 = t0 + args.seconds
        time.sleep(max(0.0, t1 - time.perf_counter()))
        off.settle(t1 + 120.0)
        soj = off.done[:off.used] - off.due[:off.used]
        q = len(soj) // 4
        first, last = np.median(soj[:q]), np.median(soj[-q:])
        done_in = np.count_nonzero((off.done >= t0) & (off.done < t1))
        print(json.dumps(dict(
            rate_qps=r, offered=len(qs), completed_qps=done_in / args.seconds,
            p50_ms=1e3 * float(np.median(soj)),
            p95_ms=1e3 * float(np.percentile(soj, 95)),
            growth=float(last / first),
            batch_q=dep.stack.window.stats["served"]
            / max(1, dep.stack.window.stats["batches"]))), flush=True)
    dep.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
