"""The lower-precision control of a cell's comparison.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--sweeps N]

The configuration states float32.  The control puts the plain reference,
computed in bfloat16, in the program's place: for each job the run would
send on that seed, its answer is the bfloat16 reference's pruned-space
optimum, with the metrics and per-operator strategies bfloat16 gives
there.  Those answers go through the same comparison as a run's
(``bench/check.py``, against the float64 reference), and must come out
not correct.  A closed-loop cell compares ``--sweeps`` sweeps (a run's
window holds about that many); an open-loop cell every arrival of a
``run_seconds`` window.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import cell as cells  # noqa: E402
import check  # noqa: E402
import loadgen  # noqa: E402
from reference import STRATEGIES, Reference  # noqa: E402


def window_jobs(cell, seed: int, sweeps: int, seconds: float) -> list:
    """``(triple, budget)`` of every job a run's window would compare."""
    config, mix = cell.config, cell.mix
    if mix["loop"] == "open":
        return [(a.triple, a.budget) for a in
                loadgen.open_arrivals(mix, config, seed, seconds)]
    budgets = loadgen.closed_sweeps(mix, config, seed)[:sweeps]
    return [(t, b) for b in budgets for t in loadgen.triples(config)]


def control_answer(low: Reference, triple, budget) -> check.Answer:
    """The low-precision reference's answer to one job."""
    net, sset, obj = triple
    cfg, _ = low.optimum(net, sset, obj, budget)
    got = low.answer(net, sset, obj, budget, cfg)
    per_op = {name: min(scores, key=lambda s: (scores[s],
                                               STRATEGIES.index(s)))
              for name, scores in got.op_scores.items()}
    return check.Answer(cfg, int(low.bw), got.metrics, per_op)


def run_control(cell, seed: int, sweeps: int,
                seconds: float) -> tuple[bool, dict]:
    ref = Reference(cell.config)
    low = Reference(cell.config, dtype="bfloat16")
    items = [(t, b, control_answer(low, t, b))
             for t, b in window_jobs(cell, seed, sweeps, seconds)]
    numbers, _ = check.compare(items, ref, cell.config["method"])
    numbers = {"missing": numbers.pop("missing"), "store_mismatch": 0,
               **numbers}
    return check.verdict(numbers, cell.limits)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sweeps", type=int, default=4)
    args = ap.parse_args(argv)
    cell = cells.load_cell(args.workload)
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        seconds = json.load(f)["run_seconds"]
    for seed in (int(s) for s in args.seeds.split(",")):
        correct, table = run_control(cell, seed, args.sweeps, seconds)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "correct": correct, "checks": table}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
