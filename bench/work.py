"""Work of the cost-evaluation executables, counted from shapes.

One *evaluation* is one (candidate, operator, strategy) cost of the closed
form.  The work is the evaluations the search algorithm needs, without the
lanes and operator rows the program pads:

- exhaustive: every pruned candidate of the job, times its merged
  operators, times the strategies its set admits;
- simulated annealing: chains x (steps + 1) candidates (one initial point
  per chain, one proposal per step), times operators times strategies.

FLOPs were counted once from the program's closed form
(``cost_model.job_objective`` traced to a jaxpr with traced macro and
technology leaves, every elementwise arithmetic, compare and select
primitive counted once per output element): 2721 for one operator,
4955 for two, 9423 for four and 18359 for eight, i.e. 2234 per operator
(eight strategies and the per-operator choice) plus 487 per candidate
(area, budget penalty, bandwidth check, objective).  Bytes are the
candidate rows in (six float32), the value out (one float32), and each
job's parameters (operator rows and about 30 scalars) once per block.
"""
from __future__ import annotations

import math

FLOPS_PER_OP_STRATEGY = 2234 / 8
FLOPS_PER_CANDIDATE = 487
BYTES_PER_CANDIDATE = (6 + 1) * 4
#: candidate block width of one exhaustive call (rows per job per call)
EXHAUSTIVE_BLOCK = 4096


def param_bytes(n_ops: int) -> int:
    return (n_ops * 5 + 30) * 4


def flops(candidates: float, n_ops: int, n_strategies: int) -> float:
    return candidates * (n_ops * n_strategies * FLOPS_PER_OP_STRATEGY
                         + FLOPS_PER_CANDIDATE)


def exhaustive_job(candidates: int, n_ops: int, n_strategies: int):
    """``(flops, bytes)`` of one job's pruned-space sweep."""
    blocks = math.ceil(candidates / EXHAUSTIVE_BLOCK)
    return (flops(candidates, n_ops, n_strategies),
            candidates * BYTES_PER_CANDIDATE + blocks * param_bytes(n_ops))


def sa_job(chains: int, steps: int, n_ops: int, n_strategies: int):
    """``(flops, bytes)`` of one job's annealing run: parameters, axis
    values and chain keys in; per-chain bests and the best trace out."""
    evals = chains * (steps + 1)
    io = param_bytes(n_ops) + 5 * 16 * 4 + chains * 2 * 2 * 4 \
        + chains * 6 * 4 + steps * 4
    return flops(evals, n_ops, n_strategies), io
