"""Read the control, or a planted fault, beside the program's own
numbers, on several seeds.

    python3 bench/control.py --workload wiki-phrase.steady \
        --seeds 11,12,13 --seconds 10 [--fault uniform]

For each seed, one run of the cell as ``bench/run.py`` makes it (set-up,
a window at the cell's own load, the check), with the named fault of
``bench/faults.py`` planted in the timed path if ``--fault`` is given.
The check runs twice on the same answers: once as the benchmark runs
it (``program``: sound, or with the fault), and once with the bfloat16
reference in the planner's place (``control``), which has to come out
not correct.  The control's reading depends only on the queries
checked, so one run gives both.  One JSON line per seed.  It runs on
the chip, one process for all the seeds; the benchmark's own runs
never run it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import faults, run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--fault", choices=sorted(faults.FAULTS))
    args = ap.parse_args()
    _, cell, cfg, traffic = run.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.run_cell(cfg, traffic, seed, args.seconds, False,
                           chips=cell["chips"], control=True,
                           hook=faults.FAULTS.get(args.fault),
                           t_start=time.perf_counter())
        if out is None:
            return 2
        line = dict(seed=seed, fault=args.fault, attempted=out["attempted"],
                    program=out["program"],
                    control=dict(correct=out["correct"],
                                 checks=out["checks"]))
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
