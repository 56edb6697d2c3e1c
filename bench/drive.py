"""The system under test, driven from the client side.

One :class:`Served` holds what a deployment of the service holds: the
default ``ExplorationEngine``, a ``JobQueue`` with the ``QueueConfig`` the
traffic mix asks for (:func:`queue_config`) and a fresh ``ResultStore`` in
a temporary directory.  Jobs
are built from the configuration file and sent through
``JobQueue.submit``/``submit_many``; every call into the service is wrapped
in a profiler annotation named for the layer it enters, so a trace can say
what the host was doing while the device idled.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time

import loadgen
import numpy as np

#: area budgets of warm-up jobs, below every window grid: a warm job never
#: answers a window job from the store
WARM_BASE_MM2 = 2.0
WARM_STEP_MM2 = 0.001


@dataclasses.dataclass
class JobRecord:
    """One job of the window: what was sent, when it was due, when sent and
    when its future resolved (perf-counter seconds)."""

    triple: tuple
    budget: float
    due: float
    sent: float = math.nan
    resolved: float | None = None
    future: object = None

    def outcome(self):
        """``(result, error)``; both ``None`` for a future that never came."""
        f = self.future
        if f is None or not f.done():
            return None, None
        err = f.exception(timeout=0)
        return (None, err) if err is not None else (f.result(timeout=0),
                                                    None)


def queue_config(mix: dict, config: dict) -> dict:
    """The ``QueueConfig`` fields of a mix; the program's defaults where it
    names none.  A closed-loop mix with ``sweep_batch_window_s`` has the
    queue close each micro-batch when one whole sweep is in
    (``max_batch_jobs`` = the jobs of a sweep), or after that many seconds:
    a sweep is then one dispatch however long its ``submit_many`` takes,
    where the default 20 ms window splits it whenever the host is slow."""
    if mix["loop"] == "closed" and "sweep_batch_window_s" in mix:
        return {"batch_window_s": float(mix["sweep_batch_window_s"]),
                "max_batch_jobs": len(loadgen.triples(config))}
    return {}


def sa_seed(seed: int) -> int:
    """The annealer's seed, drawn from the run's seed (fits 31 bits)."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0] >> 1)


class Served:
    """The service as a deployment runs it, built from a configuration."""

    def __init__(self, config: dict, seed: int, store_dir: str,
                 annotate=None, queue_config: dict | None = None):
        from repro.core import ExplorationEngine, ExploreJob
        from repro.core.annealing import SASettings
        from repro.core.calibration import TechConstants
        from repro.core.ir import MatmulOp, Workload
        from repro.core.macro import MacroSpec
        from repro.core.pruning import DesignSpace
        from repro.service import JobQueue, QueueConfig, ResultStore

        self._job_cls = ExploreJob
        self.config = config
        self.method = config["method"]
        self.macro = MacroSpec(**config["macro"])
        self.tech = TechConstants(**config["tech"])
        self.space = DesignSpace(**{k: tuple(v) for k, v in
                                    config["design_space"].items()})
        self.workloads = {
            name: Workload(name, tuple(MatmulOp(m, k, n, c, bool(s), op)
                                       for m, k, n, c, s, op in ops))
            for name, ops in config["networks"].items()}
        self.settings = None
        if self.method == "sa":
            self.settings = SASettings(seed=sa_seed(seed),
                                       **config["settings"])
        self.engine = ExplorationEngine()
        self.store = ResultStore(store_dir)
        self.queue = JobQueue(engine=self.engine, store=self.store,
                              config=QueueConfig(**(queue_config or {})))
        self.annotate = annotate or (lambda name: contextlib.nullcontext())
        self._warm = 0

    def job(self, triple, budget):
        net, sset, obj = triple
        return self._job_cls(self.macro, self.workloads[net], budget,
                             objective=obj, strategy_set=sset,
                             bw=int(self.config["bw"]), tech=self.tech,
                             space=self.space)

    def submit_many(self, pairs, method=None):
        """Send ``(triple, budget)`` pairs together; returns futures."""
        method = method or self.method
        with self.annotate("bench.submit"):
            return self.queue.submit_many(
                [self.job(t, b) for t, b in pairs], method=method,
                settings=self.settings if method == self.method else None)

    def close(self):
        with self.annotate("bench.close"):
            self.queue.close()

    # ---- set-up -------------------------------------------------------- #
    def _warm_pairs(self, triples_):
        """Fresh warm-up budgets, one per triple (never a store hit)."""
        out = []
        for t in triples_:
            out.append((t, round(WARM_BASE_MM2 + WARM_STEP_MM2 * self._warm,
                                 6)))
            self._warm += 1
        return out

    def _wait(self, futures, timeout=1200.0):
        with self.annotate("bench.wait"):
            for f in futures:
                f.result(timeout=timeout)

    def warm_up(self, mix: dict) -> None:
        """Run every shape the window will use once: a sweep of every
        triple (the shared executables and each network's epilogue), the
        one-job exhaustive sweep a stochastic search falls back to, and,
        for open traffic, each jobs-per-dispatch count up to the mix's
        ``warm_jobs_per_dispatch`` in every executable bucket."""
        all_t = loadgen.triples(self.config)
        if self.method != "exhaustive":
            for group in self._buckets(all_t, "exhaustive"):
                self._wait(self.submit_many(self._warm_pairs(group[:1]),
                                            method="exhaustive"))
        for n in range(1, int(mix.get("warm_jobs_per_dispatch", 0)) + 1):
            for group in self._buckets(all_t, self.method):
                picks = [group[i % len(group)] for i in range(n)]
                self._wait(self.submit_many(self._warm_pairs(picks)))
        # the full sweep comes last: it leaves every network's eager
        # epilogue the most recently used in JAX's bounded caches
        for b in mix["warm_budgets_mm2"]:
            self._wait(self.submit_many([(t, b) for t in all_t]))

    def _buckets(self, triples_, method) -> list:
        groups: dict = {}
        for t in triples_:
            key = self.engine.bucket_key(self.job(t, 5.0), method)
            groups.setdefault(key, []).append(t)
        return list(groups.values())


def _stamp(rec: JobRecord):
    def done(_future):
        rec.resolved = time.perf_counter()
    return done


def closed_window(served: Served, mix: dict, seed: int,
                  seconds: float) -> tuple[list, float]:
    """Sweeps back to back from one client until ``seconds`` have passed;
    the window closes when the sweep running at that moment resolves.
    Returns the records and the window's length."""
    all_t = loadgen.triples(served.config)
    records: list[JobRecord] = []
    t0 = time.perf_counter()
    for budget in loadgen.closed_sweeps(mix, served.config, seed):
        now = time.perf_counter()
        if now - t0 >= seconds:
            break
        recs = [JobRecord(t, budget, due=now) for t in all_t]
        futures = served.submit_many([(t, budget) for t in all_t])
        sent = time.perf_counter()
        for rec, fut in zip(recs, futures):
            rec.sent, rec.future = sent, fut
            fut.add_done_callback(_stamp(rec))
        records.extend(recs)
        deadline = t0 + seconds + 60.0
        with served.annotate("bench.wait"):
            if not all(f.wait(max(0.0, deadline - time.perf_counter()))
                       for f in futures):
                break
    ends = [r.resolved for r in records if r.resolved is not None]
    return records, (max(ends) if ends else time.perf_counter()) - t0


def open_window(served: Served, mix: dict, seed: int,
                seconds: float) -> tuple[list, float]:
    """Single jobs at the mix's fixed rate for ``seconds``, each sent at
    its due time (or as soon after as the sender can); then every future is
    awaited up to a minute past the close."""
    arrivals = loadgen.open_arrivals(mix, served.config, seed, seconds)
    records: list[JobRecord] = []
    t0 = time.perf_counter()
    for a in arrivals:
        due = t0 + a.due_s
        pause = due - time.perf_counter()
        if pause > 0:
            time.sleep(pause)
        rec = JobRecord(a.triple, a.budget, due=due)
        rec.sent = time.perf_counter()
        (rec.future,) = served.submit_many([(a.triple, a.budget)])
        rec.future.add_done_callback(_stamp(rec))
        records.append(rec)
    close = t0 + seconds
    with served.annotate("bench.wait"):
        time.sleep(max(0.0, close - time.perf_counter()))
        for rec in records:
            rec.future.wait(max(0.0, close + 60.0 - time.perf_counter()))
    return records, seconds
