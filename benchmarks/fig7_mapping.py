"""Paper Fig. 7: CIM-Tuner's scheduling+tiling (ST) space vs the spatial-
only (SO) mapping of [19], under the SAME hardware-mapping co-exploration
with a 5 mm^2 budget, across the seven evaluation networks.

Paper claims: average 1.58x energy efficiency and 2.11x throughput.

All 28 (network x strategy-set x objective) jobs are submitted to the async
DSE service in one shot; ``run()`` is a *generator* that yields each
network's row the moment its four jobs complete (networks sharing an
executable bucket finish together, so rows stream out bucket by bucket
instead of blocking on the slowest network).  A 4-job subset is also timed
against the sequential retrace-per-job path to report the engine's
end-to-end speedup.

``--search`` instead races the pluggable ``repro.search`` backends (SA /
GA / DE / Sobol, plus the portfolio under BOTH budget allocators --
fixed-rung successive halving and the UCB bandit -- each at its default
evaluation budget) on the same co-exploration jobs: per network it prints
each backend's best-found objective, its gap to the exhaustive ground
truth, its allocator column (``alloc=-`` for non-composite backends), and
the measured wall-clock.  The bandit row is the acceptance check for the
allocator upgrade: it must match exhaustive on bert-large at wall-clock
less than or equal to the fixed-rung portfolio's.
"""
from __future__ import annotations

import time
import typing

from benchmarks.common import SEVEN_WORKLOADS, csv_line, geomean, get_workload, timed
from repro.core import ExplorationEngine, ExploreJob, get_macro
from repro.service import ServiceClient, as_completed

BUDGET = 5.0
STREAM_TIMEOUT_S = 1800.0
#: networks used for the --search backend race (first two of Fig. 7)
SEARCH_NETWORKS = ("bert-large", "yi-6b")
SEARCH_BACKENDS = ("sa", "genetic", "evolution", "sobol")
#: the portfolio races once per budget allocator (the bandit is the
#: default; "halving" is the fixed-rung baseline it must not lose to)
PORTFOLIO_ALLOCATORS = ("halving", "bandit")


def fig7_jobs(macro):
    """The 28 Fig. 7 jobs (seven networks x {so, st} x {ee, th}, 5 mm^2)
    and their (network, strategy set, objective) metas."""
    jobs, meta = [], []
    for name in SEVEN_WORKLOADS:
        wl = get_workload(name)
        for sset in ("so", "st"):
            for obj in ("ee", "th"):
                jobs.append(ExploreJob(macro, wl, BUDGET, objective=obj,
                                       strategy_set=sset))
                meta.append((name, sset, obj))
    return jobs, meta


def _speedup_lines(macro) -> list[str]:
    """4-job sweep: batched engine vs the sequential per-job path (fresh
    objective rebuilt + re-traced per job, i.e. executable cache off).

    Both legs share the persistent XLA compile cache (warm by this point),
    so the ratio isolates the per-job retrace/dispatch cost the engine
    removes; on a cold machine the sequential leg additionally pays one
    XLA compile per job and the gap widens."""
    sub = []
    for name in SEVEN_WORKLOADS[:4]:
        sub.append(ExploreJob(macro, get_workload(name), BUDGET,
                              objective="ee", strategy_set="st"))

    def sequential():
        out = []
        for job in sub:
            eng = ExplorationEngine(executable_cache=False)
            out.extend(eng.run([job], method="exhaustive"))
        return out

    def batched():
        return ExplorationEngine().run(sub, method="exhaustive")

    seq_res, t_seq = timed(sequential)
    bat_res, t_bat = timed(batched)
    assert [r.config.as_tuple() for r in seq_res] == \
        [r.config.as_tuple() for r in bat_res], "engine/sequential mismatch"
    return [csv_line(
        "fig7_batching_speedup", t_bat * 1e6,
        f"4-job sweep sequential(retrace-per-job) {t_seq:.1f}s -> batched "
        f"{t_bat:.1f}s (x{t_seq / t_bat:.1f} end-to-end, target >=2x, "
        f"identical configs, shared warm compile cache)")]


def run() -> typing.Iterator[str]:
    macro = get_macro("vanilla-dcim")
    svc = ServiceClient(engine=ExplorationEngine())
    try:
        jobs, meta = fig7_jobs(macro)
        t0 = time.perf_counter()
        futures = svc.submit_many(jobs, method="exhaustive", metas=meta)

        per_net: dict[str, dict] = {name: {} for name in SEVEN_WORKLOADS}
        ee_gains, th_gains = [], []
        t_last = t0
        for fut in as_completed(futures, timeout=STREAM_TIMEOUT_S):
            name, sset, obj = fut.meta
            per_net[name][(sset, obj)] = fut.result()
            if len(per_net[name]) < 4:
                continue
            got = per_net[name]
            out = {
                sset: {"tops_w": got[(sset, "ee")].metrics["tops_w"],
                       "gops": got[(sset, "th")].metrics["gops"]}
                for sset in ("so", "st")
            }
            ee_gain = out["st"]["tops_w"] / out["so"]["tops_w"]
            th_gain = out["st"]["gops"] / out["so"]["gops"]
            ee_gains.append(ee_gain)
            th_gains.append(th_gain)
            # us_per_call = marginal wall-clock to produce THIS row in the
            # stream (sums to total; same-bucket siblings arrive ~free)
            t_now = time.perf_counter()
            dt_row, t_last = t_now - t_last, t_now
            yield csv_line(
                f"fig7_{name}", dt_row * 1e6,
                f"EE {out['so']['tops_w']:.2f}->{out['st']['tops_w']:.2f} "
                f"TOPS/W (x{ee_gain:.2f})  "
                f"Th {out['so']['gops']:.0f}->{out['st']['gops']:.0f} GOPS "
                f"(x{th_gain:.2f})")
        dt = time.perf_counter() - t0
        yield csv_line(
            "fig7_average", 0.0,
            f"EE_gain_geomean=x{geomean(ee_gains):.2f} (paper x1.58)  "
            f"Th_gain_geomean=x{geomean(th_gains):.2f} (paper x2.11)  "
            f"[{len(jobs)} jobs in {dt:.1f}s via service: "
            f"{svc.stats['dispatches']} dispatches, "
            f"{svc.stats['store_hits']} store hits, "
            f"{svc.stats['inflight_dedup']} deduped]")
    finally:
        svc.close()
    yield from _speedup_lines(macro)


def run_search(
    networks: typing.Sequence[str] = SEARCH_NETWORKS,
    backends: typing.Sequence[str] | None = None,
    fidelity: str = "analytic",
) -> typing.Iterator[str]:
    """Backend race: best-found objective + wall-clock per ``repro.search``
    backend (portfolio rows once per budget allocator), against the
    exhaustive ground truth, one engine per race so every backend pays its
    own compile exactly once.  Every row carries an ``alloc=`` column.

    ``backends`` restricts the race (``None`` = all); ``fidelity`` other
    than ``"analytic"`` (``"two"``/``"measured"``) runs the portfolio as a
    two-fidelity race whose final rung re-scores the top-K analytic
    winners with measured Pallas kernel timings -- its rows then carry
    ``rank_corr=`` plus both rankings (see docs/calibration.md)."""
    from repro.search import PortfolioSettings

    chosen = set(backends) if backends else None
    fidelity = {"two": "measured"}.get(fidelity, fidelity)
    measured = fidelity != "analytic"
    macro = get_macro("vanilla-dcim")
    engine = ExplorationEngine()
    for name in networks:
        job = ExploreJob(macro, get_workload(name), BUDGET,
                         objective="ee", strategy_set="st")
        (ex,), t_ex = timed(engine.run, [job], method="exhaustive")
        yield csv_line(
            f"fig7_search_{name}_exhaustive", t_ex * 1e6,
            f"alloc=- energy={ex.metrics['energy_pj']:.6g} pJ "
            f"EE={ex.metrics['tops_w']:.2f} TOPS/W "
            f"(ground truth, wall {t_ex:.2f}s)")
        races: list[tuple[str, str | None]] = \
            [(b, None) for b in SEARCH_BACKENDS
             if chosen is None or b in chosen] + \
            ([("portfolio", alloc) for alloc in PORTFOLIO_ALLOCATORS]
             if chosen is None or "portfolio" in chosen else [])
        best_name, best_energy = None, float("inf")
        wall: dict[str, float] = {}
        for backend, alloc in races:
            settings = None if alloc is None else \
                PortfolioSettings(allocator=alloc,
                                  fidelity=fidelity if measured
                                  else "analytic")
            (res,), t_b = timed(engine.run, [job], method=backend,
                                settings=settings)
            row = backend if alloc is None else f"{backend}_{alloc}"
            wall[row] = t_b
            energy = res.metrics["energy_pj"]
            tf = res.search.get("two_fidelity") \
                if backend == "portfolio" else None
            # measured-fidelity metrics carry calibrated energy constants
            # -- a different unit system than the analytic exhaustive
            # reference, so the gap column and the cross-backend best-of
            # would compare apples to oranges
            if tf is None:
                if energy < best_energy:
                    best_name, best_energy = row, energy
                gap_txt = (f"(gap "
                           f"{(energy / ex.metrics['energy_pj'] - 1) * 100:+.3f}% "
                           f"vs exhaustive) ")
            else:
                gap_txt = "(calibrated units; gap n/a) "
            extra = ""
            if backend == "portfolio":
                pf = res.search["portfolio"]
                extra = f" winner={pf['winner']} devices={pf['devices']}"
                if tf is not None:
                    extra += (
                        f" rank_corr={tf['rank_correlation']:.3f}"
                        f" topk={tf['topk']}"
                        f" analytic_rank={tf['analytic_ranking']}"
                        f" measured_rank={tf['measured_ranking']}"
                        f" calib={tf['source']}")
            yield csv_line(
                f"fig7_search_{name}_{row}", t_b * 1e6,
                f"alloc={alloc or '-'} energy={energy:.6g} pJ "
                f"{gap_txt}"
                f"EE={res.metrics['tops_w']:.2f} TOPS/W "
                f"wall={t_b:.2f}s{extra}")
        if {"portfolio_bandit", "portfolio_halving"} <= wall.keys():
            speed = wall["portfolio_halving"] / wall["portfolio_bandit"]
            yield csv_line(
                f"fig7_search_{name}_allocators",
                wall["portfolio_bandit"] * 1e6,
                f"alloc=bandit-vs-halving bandit {wall['portfolio_bandit']:.2f}s "
                f"vs halving {wall['portfolio_halving']:.2f}s "
                f"(x{speed:.2f})")
        if best_name is not None:
            yield csv_line(
                f"fig7_search_{name}_best", 0.0,
                f"alloc=- best backend={best_name} "
                f"energy={best_energy:.6g} pJ")


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--search", nargs="?", const="all", default=None,
                    metavar="BACKENDS",
                    help="race the repro.search backends instead of the "
                         "ST-vs-SO sweep; optional comma-separated subset "
                         "(e.g. 'portfolio' or 'sa,sobol'; default: all)")
    ap.add_argument("--fidelity", choices=("analytic", "two", "measured"),
                    default="analytic",
                    help="'two'/'measured': the portfolio's final rung "
                         "re-scores top-K analytic winners with measured "
                         "Pallas kernel timings and rows report "
                         "rank_corr= (default: analytic)")
    ap.add_argument("--networks", default=",".join(SEARCH_NETWORKS),
                    help="comma-separated networks for --search")
    args = ap.parse_args()
    if args.search is not None:
        backends = None if args.search == "all" \
            else tuple(b for b in args.search.split(",") if b)
        lines = run_search(tuple(args.networks.split(",")),
                           backends=backends, fidelity=args.fidelity)
    else:
        lines = run()
    for line in lines:
        print(line, flush=True)
