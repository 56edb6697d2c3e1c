#!/usr/bin/env python3
"""Drive the co-exploration service once on a TPU and check what it returns.

    python chip_smoke.py             # one chip: phases 1-6 below
    python chip_smoke.py --chips 4   # four chips: the multi-device paths only

One process drives the chip and starts no children.  With one chip it runs
the paper's Fig. 7 deployment (seven networks x {so, st} x {ee, th} at
5 mm^2, the full design space, exhaustive search) through the normal entry
points:

1. device check: the first JAX device must be a TPU, else exit non-zero;
2. the 28 jobs through ``JobQueue.submit_many`` on a fresh result store,
   then a second, warm submission on another fresh store;
3. the same jobs on an in-process CPU engine in float32 and in x64: every
   chip config must equal the CPU float32 one; relative errors against
   x64 are printed for both;
4. a portfolio race (bandit allocator, default settings) on 4 of the jobs,
   checked against the exhaustive optimum;
5. the HTTP front door in this process: 2 job specs through
   ``ServiceClient(base_url=...)``, results read back over SSE;
6. the four Pallas kernels through ``repro.kernels.ops`` at real widths,
   compiled (``tpu_custom_call`` in the lowering) and checked against
   ``repro.kernels.ref``.

``--chips 4`` races the phase-4 jobs across four chips against one device
(results must be bit-identical, dispatches must land on at least two
devices) and runs the sharded annealer on a 4-device mesh beside a
1-device mesh.

Any failed phase exits non-zero.  The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

#: the phase-4 race jobs: the two networks ``fig7_mapping.py --search``
#: races, under both objectives
RACE_JOBS = (("bert-large", "st", "ee"), ("bert-large", "st", "th"),
             ("yi-6b", "st", "ee"), ("yi-6b", "st", "th"))
#: the phase-5 HTTP jobs: one per executable bucket
HTTP_JOBS = (("whisper-small", "st", "ee"), ("bert-large", "so", "th"))
RESULT_TIMEOUT_S = 900.0


class SmokeFailure(RuntimeError):
    """A phase returned a wrong or missing result."""


def log(msg: str) -> None:
    print(msg, flush=True)


def require(ok: bool, what: str) -> None:
    """Fail the smoke (``assert`` would vanish under ``python -O``)."""
    if not ok:
        raise SmokeFailure(what)


def device_check(chips: int) -> list:
    """Phase 1: a TPU, with at least ``chips`` devices, or exit."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{devices[0].platform!r} ({devices[0]})")
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke: needs {chips} chips, JAX found "
                         f"{len(devices)}")
    log(f"[1] device: {devices[0].device_kind} x{len(devices)}")
    return devices


def objective_metric(result) -> float:
    """The modelled quantity a job minimizes."""
    key = "energy_pj" if result.objective == "ee" else "latency_cycles"
    return result.metrics[key]


def _compile_seconds() -> float:
    from repro import obs

    return obs.registry().snapshot().get("cim_engine_compile_seconds_sum",
                                         0.0)


def _submit(engine, jobs, metas, store_dir, method):
    from repro.service import JobQueue, ResultStore

    queue = JobQueue(engine=engine, store=ResultStore(store_dir))
    t0 = time.perf_counter()
    futures = queue.submit_many(jobs, method=method, metas=metas)
    results = [f.result(timeout=RESULT_TIMEOUT_S) for f in futures]
    wall = time.perf_counter() - t0
    queue.close()
    return results, wall, queue.stats["dispatches"]


def fig7_service(engine, jobs, metas, store_root):
    """Phase 2: the 28 exhaustive jobs through the queue, cold then warm."""
    compile0 = _compile_seconds()
    results, cold_s, dispatches = _submit(
        engine, jobs, metas, os.path.join(store_root, "cold"), "exhaustive")
    misses = engine.stats["executable_cache_misses"]
    require(dispatches > 0 and misses > 0,
            f"no device work: dispatches={dispatches} cache misses={misses}")
    for (name, sset, obj), r in zip(metas, results):
        log(f"[2] {name:<21} {sset}/{obj} cfg={r.config.as_tuple()} "
            f"cycles={r.metrics['latency_cycles']!r} "
            f"pJ={r.metrics['energy_pj']!r} "
            f"mm2={r.metrics['area_mm2']!r}")
    compile_s = _compile_seconds() - compile0
    warm, warm_s, warm_dispatches = _submit(
        engine, jobs, metas, os.path.join(store_root, "warm"), "exhaustive")
    require(warm_dispatches > 0, "warm submission did no device work")
    for a, b in zip(results, warm):
        require((a.config, a.metrics) == (b.config, b.metrics),
                f"warm rerun differs: {a.summary()} vs {b.summary()}")
    log(f"[2] {len(jobs)} jobs: cold wall {cold_s!r} s, "
        f"cim_engine_compile_seconds {compile_s!r} s, "
        f"dispatches {dispatches}, executable-cache misses {misses}; "
        f"warm wall (fresh store) {warm_s!r} s, dispatches "
        f"{warm_dispatches}")
    return results


def _rel(a: float, b: float) -> float:
    return abs(a - b) / abs(b) if b else abs(a - b)


def cpu_reference(jobs, metas, chip):
    """Phase 3: the same jobs on the CPU in float32 and in x64."""
    import jax
    import numpy as np

    from repro.compat import enable_x64
    from repro.core import ExplorationEngine

    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        f32 = ExplorationEngine().run(jobs, method="exhaustive")
        with enable_x64(True):
            x64 = ExplorationEngine().run(jobs, method="exhaustive")
    worst = {"chip": 0.0, "cpu32": 0.0}
    mismatched = []
    for i, ((name, sset, obj), c, f, x) in enumerate(
            zip(metas, chip, f32, x64)):
        errs = {}
        for tag, r in (("chip", c), ("cpu32", f)):
            e_cyc = _rel(r.metrics["latency_cycles"],
                         x.metrics["latency_cycles"])
            e_pj = _rel(r.metrics["energy_pj"], x.metrics["energy_pj"])
            errs[tag] = (e_cyc, e_pj)
            worst[tag] = max(worst[tag], e_cyc, e_pj)
        same = c.config == f.config
        if not same:
            mismatched.append(i)
        log(f"[3] {name:<21} {sset}/{obj} "
            f"chip-vs-x64 cycles {errs['chip'][0]:.3e} pJ "
            f"{errs['chip'][1]:.3e} | cpu32-vs-x64 cycles "
            f"{errs['cpu32'][0]:.3e} pJ {errs['cpu32'][1]:.3e} | "
            f"cfg chip==cpu32 {same} chip==x64 {c.config == x.config}")
    for i in mismatched:
        # which of the two configs is really better: both under x64
        rows = np.array([[*r.config.as_tuple(), r.config.bw]
                         for r in (chip[i], f32[i])], np.float64)
        with jax.default_device(cpu), enable_x64(True):
            vals = ExplorationEngine().candidate_values([jobs[i]], [rows])[0]
        log(f"[3] MISMATCH {metas[i]}: chip {chip[i].config.as_tuple()} "
            f"cpu32 {f32[i].config.as_tuple()} x64 objective "
            f"{vals[0]!r} vs {vals[1]!r}")
    log(f"[3] largest relative error vs x64: chip {worst['chip']:.3e}, "
        f"cpu float32 {worst['cpu32']:.3e}; config mismatches chip vs "
        f"cpu float32: {len(mismatched)}")
    require(not mismatched,
            f"{len(mismatched)} chip configs differ from the CPU's")


def _pick(metas, wanted):
    return [metas.index(w) for w in wanted]


def portfolio_race(engine, jobs, metas, exhaustive, store_root):
    """Phase 4: bandit portfolio on 4 jobs, against the exhaustive optimum."""
    idx = _pick(metas, RACE_JOBS)
    results, wall, dispatches = _submit(
        engine, [jobs[i] for i in idx], [metas[i] for i in idx],
        os.path.join(store_root, "portfolio"), "portfolio")
    for i, r in zip(idx, results):
        ex = exhaustive[i]
        pf = r.search["portfolio"]
        require(pf["allocator"] == "bandit", f"allocator {pf['allocator']}")
        gap = objective_metric(r) / objective_metric(ex) - 1.0
        log(f"[4] {metas[i][0]:<21} {metas[i][1]}/{metas[i][2]} "
            f"winner={pf['winner']} cfg={r.config.as_tuple()} gap "
            f"{gap * 100:+.4f}% vs exhaustive {ex.config.as_tuple()}")
        require(gap >= -1e-6, f"portfolio beat the exhaustive optimum: {gap}")
        require(r.metrics["area_mm2"] <= jobs[i].area_budget_mm2 * 1.001,
                f"portfolio winner over budget: {r.summary()}")
    log(f"[4] 4 portfolio jobs in {wall!r} s, dispatches {dispatches}")


def http_front_door(engine, jobs, metas, exhaustive, store_root):
    """Phase 5: 2 job specs over HTTP + SSE to an in-process server."""
    from repro.service import ResultStore, ServiceClient
    from repro.service.client import job_to_spec
    from repro.service.server import DSEServer, ServerConfig

    idx = _pick(metas, HTTP_JOBS)
    server = DSEServer(engine=engine,
                       store=ResultStore(os.path.join(store_root, "http")),
                       config=ServerConfig(port=0)).start()
    try:
        client = ServiceClient(base_url=server.url, store=None)
        t0 = time.perf_counter()
        got = client.explore_specs(
            [job_to_spec(jobs[i], "exhaustive") for i in idx],
            timeout=RESULT_TIMEOUT_S)
        wall = time.perf_counter() - t0
        posted = client.stats["posted"]
        client.close()
    finally:
        server.shutdown()
    require(posted == len(idx), f"expected {len(idx)} POSTed jobs: {posted}")
    for i, r in zip(idx, got):
        ex = exhaustive[i]
        require((r.config, r.metrics) == (ex.config, ex.metrics),
                f"HTTP result differs: {r.summary()} vs {ex.summary()}")
        log(f"[5] {metas[i][0]:<21} {metas[i][1]}/{metas[i][2]} over "
            f"HTTP+SSE cfg={r.config.as_tuple()} == phase 2")
    log(f"[5] {len(idx)} specs via {server.url} in {wall!r} s")


def _lowered(fn, *args) -> str:
    import jax

    return jax.jit(fn).lower(*args).as_text()


def _max_err(got, want) -> float:
    """Largest absolute error over the reference's largest magnitude."""
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1.0))


def kernels(seed: int):
    """Phase 6: the four Pallas kernels compiled, against their oracles."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import get_workload
    from repro.core.macro import get_macro
    from repro.core.pruning import (
        DesignSpace,
        candidates_with_bw,
        enumerate_space,
    )
    from repro.kernels import ops, ref

    rng = np.random.default_rng(seed)

    def normal(shape, dtype=jnp.float32):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    def check(name, fn, args, want, tol):
        text = _lowered(fn, *args)
        require("tpu_custom_call" in text, f"{name} did not compile")
        got = jax.block_until_ready(fn(*args))
        err = _max_err(got, want)
        log(f"[6] {name}: tpu_custom_call, max error {err:.3e} "
            f"(limit {tol:g})")
        require(err <= tol, f"{name} disagrees with its oracle: {err}")

    with jax.default_matmul_precision("highest"):
        a = normal((1024, 1024), jnp.bfloat16)
        b = normal((1024, 1024), jnp.bfloat16)
        want = ref.matmul_ref(a, b)
        for tiling in ("AF", "PF"):
            check(f"cim_matmul {tiling} 1024x1024x1024 bf16",
                  lambda x, y, t=tiling: ops.cim_matmul(x, y, tiling=t),
                  (a, b), want, 2e-2)

        q, k, v = (normal((8, 1024, 128), jnp.bfloat16) for _ in range(3))
        check("flash_attention 8x1024x128 bf16", ops.flash_attention,
              (q, k, v), ref.attention_ref(q, k, v), 3e-2)

        # falcon-mamba-7b: d_inner 8192, d_state 16; 512 steps
        bs, t, i, s = 1, 512, 8192, 16
        scan_args = (normal((bs, t, i)),
                     jnp.abs(normal((bs, t, i))) * 0.1,
                     normal((bs, t, s)), normal((bs, t, s)),
                     -jnp.abs(normal((i, s))), jnp.zeros((bs, i, s)))
        y_ref, h_ref = ref.selective_scan_ref(*scan_args)
        check("selective_scan y 1x512x8192 S16",
              lambda *xs: ops.selective_scan(*xs)[0], scan_args, y_ref,
              1e-3)
        check("selective_scan h_last",
              lambda *xs: ops.selective_scan(*xs)[1], scan_args, h_ref,
              1e-3)

    macro = get_macro("vanilla-dcim")
    cands = jnp.asarray(candidates_with_bw(
        enumerate_space(DesignSpace()), 256)[:4096], jnp.float32)
    wl = jnp.asarray(get_workload("bert-large").merged().as_arrays())
    fn = lambda c, o: ops.strategy_eval(c, o, macro)
    text = _lowered(fn, cands, wl)
    require("tpu_custom_call" in text, "strategy_eval did not compile")
    got = np.asarray(fn(cands, wl))
    want = np.asarray(ref.strategy_eval_ref(cands, wl, macro))
    rel = np.abs(got - want) / np.abs(want)
    log(f"[6] strategy_eval 4096 candidates x bert-large: "
        f"tpu_custom_call, max relative error {rel.max():.3e} "
        f"(limit 1e-5), exact {np.mean(got == want):.4f}")
    require(rel.max() <= 1e-5, f"strategy_eval disagrees: {rel.max()}")


def one_chip(seed: int) -> None:
    from benchmarks.fig7_mapping import fig7_jobs
    from repro.core import ExplorationEngine
    from repro.core.macro import get_macro

    jobs, metas = fig7_jobs(get_macro("vanilla-dcim"))
    engine = ExplorationEngine()
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as store_root:
        exhaustive = fig7_service(engine, jobs, metas, store_root)
        cpu_reference(jobs, metas, exhaustive)
        portfolio_race(engine, jobs, metas, exhaustive, store_root)
        http_front_door(engine, jobs, metas, exhaustive, store_root)
    kernels(seed)


def four_chips(devices) -> None:
    """The portfolio device race and the sharded annealer on four chips,
    each beside its one-device counterpart."""
    import numpy as np

    from benchmarks.fig7_mapping import fig7_jobs
    from repro.compat import make_mesh
    from repro.core import ExplorationEngine
    from repro.core.distributed import distributed_co_explore_jobs
    from repro.core.macro import get_macro

    jobs, metas = fig7_jobs(get_macro("vanilla-dcim"))
    sub = [jobs[i] for i in _pick(metas, RACE_JOBS)]
    raced = ExplorationEngine()
    single = ExplorationEngine(device_race=False)
    t0 = time.perf_counter()
    r4 = raced.run(sub, method="portfolio")
    t4 = time.perf_counter() - t0
    t0 = time.perf_counter()
    r1 = single.run(sub, method="portfolio")
    t1 = time.perf_counter() - t0
    for meta, a, b in zip(RACE_JOBS, r4, r1):
        pa, pb = a.search["portfolio"], b.search["portfolio"]
        require((a.config, a.metrics) == (b.config, b.metrics),
                f"{meta}: race {a.summary()} vs one device {b.summary()}")
        require((pa["race"], pa["pulls"], pa["final"], pa["winner"]) ==
                (pb["race"], pb["pulls"], pb["final"], pb["winner"]),
                f"{meta}: race record differs from one device")
        log(f"[4x] {meta} cfg={a.config.as_tuple()} devices "
            f"{pa['devices']} vs {pb['devices']}: bit-identical")
    by_device = dict(raced.race_dispatch_devices)
    log(f"[4x] race dispatches {raced.stats['device_race_dispatches']} "
        f"by device {by_device}; wall 4 chips {t4!r} s, 1 device {t1!r} s")
    require(raced.stats["device_race_dispatches"] > 0,
            "no portfolio wave was placed on a race device")
    require(len(by_device) >= 2, f"race ran on {sorted(by_device)} only")
    require(single.stats["device_race_dispatches"] == 0,
            "device_race=False still raced")

    exhaustive = single.run(sub, method="exhaustive")
    mesh4 = make_mesh((4,), ("pod",), devices=devices[:4])
    mesh1 = make_mesh((1,), ("pod",), devices=devices[:1])
    d4 = distributed_co_explore_jobs(mesh4, sub, rounds=4)
    d1 = distributed_co_explore_jobs(mesh1, sub, rounds=4)
    for meta, a, b, ex in zip(RACE_JOBS, d4, d1, exhaustive):
        require(a.n_chains == 4 * b.n_chains,
                f"chains {a.n_chains} on 4 devices vs {b.n_chains} on 1")
        require(np.isfinite(a.best_value) and np.isfinite(b.best_value),
                f"{meta}: annealer found no feasible point")
        log(f"[4x] annealer {meta}: 4-device mesh {a.config.as_tuple()} "
            f"value {a.best_value!r} ({a.n_chains} chains), 1-device "
            f"{b.config.as_tuple()} value {b.best_value!r} "
            f"({b.n_chains} chains), exhaustive {ex.config.as_tuple()}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the full smoke on one chip; 4: only the "
                         "multi-chip paths and their one-device baselines")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the kernels' random inputs")
    args = ap.parse_args(argv)
    devices = device_check(args.chips)
    if args.chips == 4:
        four_chips(devices)
    else:
        one_chip(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
