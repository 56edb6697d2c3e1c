"""Batched multi-job hardware-mapping co-exploration engine.

The paper's workflow evaluates one (macro, workload, objective) job at a
time; every sweep-style consumer (Fig. 7's seven networks, Table II's two
baselines x two objectives, macro-library selection, Pareto frontiers)
therefore used to rebuild and re-jit the objective per job -- wall-clock was
dominated by retrace/recompile, not search.  This module batches whole job
lists through shared compiled executables:

1. **Shape bucketing** -- each job's merged operator array is padded to a
   small set of power-of-two widths (padded rows carry ``count == 0`` and are
   cost-transparent), and its design-space axis matrix is padded likewise, so
   heterogeneous jobs share one executable signature.
2. **Job stacking** -- macro/tech constants, strategy masks, objective codes,
   area budgets and bus widths become per-job arrays
   (:class:`repro.core.cost_model.JobParams`) vmapped over a stacked job
   axis: every ``repro.search`` backend (SA chains, GA / DE populations,
   Sobol sweeps) runs *all jobs in one jitted call*, and exhaustive sweeps
   evaluate a ``[jobs, chunk]`` candidate block per call.
3. **Two-level caching** -- an in-process executable cache keyed by (bucket
   shape, backend, settings, x64 mode) means repeated submissions never
   retrace, and JAX's persistent compilation cache is switched on by default
   (:func:`enable_persistent_compilation_cache`) so fresh processes -- CI
   runs, benchmark re-runs -- reuse compiles from disk.

The search method is pluggable (``repro.search``): any registered backend
name is a valid ``method=`` -- ``"sa"``, ``"genetic"``, ``"evolution"``,
``"sobol"`` run as one vmapped executable per shape bucket, the composite
``"portfolio"`` races them per job with a bandit (UCB) or
successive-halving budget allocator
(:meth:`ExplorationEngine._run_portfolio_batch`), re-using the constituent
backends' executables -- and, when several JAX devices are visible,
dispatching the constituents round-robin *across devices* with a per-rung
best exchange (single-device processes take the same code path with no
placement).  ``"exhaustive"`` sweeps the pruned space.
``ExploreJob.search_method`` / ``ExploreJob.search_settings`` carry the
per-job method and backend settings when no explicit ``method=`` /
``settings=`` is given (so one batch may mix methods AND settings), and
:func:`job_key` folds (method, settings) into the canonical identity so
cached results never cross backends or settings.

Identical jobs inside one ``run()`` (same canonical :func:`job_key`)
evaluate once and fan the result out.  ``co_explore`` / ``co_explore_macros``
/ ``pareto_explore`` (``core/explorer.py``) are thin synchronous clients of
the async DSE service (``repro.service``) built on this engine;
``benchmarks/fig7_mapping.py`` prints the measured batched-vs-sequential
speedup (and ``--search`` races the backends).  ``core/distributed.py``
shards the same job x chain population across devices.
"""
from __future__ import annotations

import collections
import copy
import dataclasses
import hashlib
import json
import os
import threading
import time
import typing

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import cost_model
from repro.core.annealing import SASettings, _axes_matrix
from repro.core.calibration import DEFAULT_TECH, TechConstants
from repro.core.ir import Workload
from repro.core.macro import MacroSpec
from repro.core.pruning import DesignSpace, prune_rows
from repro.core.strategies import ALL_STRATEGIES
from repro.core.template import AcceleratorConfig, accelerator_area_mm2
from repro.search.base import SearchResult, available_backends, get_backend

__all__ = [
    "ExploreJob",
    "ExploreResult",
    "ExplorationEngine",
    "default_engine",
    "enable_persistent_compilation_cache",
    "job_key",
    "preferred_settings",
    "valid_methods",
]


# --------------------------------------------------------------------- #
# telemetry families (process-wide; see docs/observability.md)
# --------------------------------------------------------------------- #
_REG = obs.registry()
_LOG = obs.get_logger("engine")
_M_JOBS = _REG.counter(
    "cim_engine_jobs_total", "Jobs submitted to ExplorationEngine.run")
_M_BATCHES = _REG.counter(
    "cim_engine_batches_total", "Batched executable dispatches")
_M_DEDUP = _REG.counter(
    "cim_engine_dedup_hits_total",
    "In-batch duplicate jobs folded into one evaluation")
_M_EXEC = _REG.counter(
    "cim_engine_executable_cache_events_total",
    "Executable-cache lookups by outcome", ("outcome",))
_M_RACE = _REG.counter(
    "cim_engine_device_race_dispatches_total",
    "Portfolio waves placed on a non-default device")
_M_RUN_S = _REG.histogram(
    "cim_engine_run_seconds", "Wall-clock of ExplorationEngine.run calls")
_M_COMPILE_S = _REG.histogram(
    "cim_engine_compile_seconds",
    "Executable calls that re-traced: trace + XLA compile (or a load "
    "from the persistent compile cache) latency")
_M_TRACES = _REG.counter(
    "cim_engine_traces_total",
    "Traces of each engine executable's Python body (one per new "
    "jobs-per-dispatch count or shape)", ("executable",))
_M_TRACES.inc(0, executable="one_job_sweep")  # eager: present when idle
_M_PHASE_S = _REG.histogram(
    "cim_engine_phase_seconds",
    "Wall-clock of the engine's phases (prepare, bucket, prune, "
    "executable, finish)", ("phase",))
#: one histogram child per phase; the phases never nest in one another
_PHASES = {ph: _M_PHASE_S.labels(phase=ph) for ph in
           ("prepare", "bucket", "prune", "executable", "finish")}
_M_PULLS = _REG.counter(
    "cim_search_pulls_total",
    "Portfolio pulls granted per backend by the budget allocator",
    ("backend", "allocator"))
_M_RUNGS = _REG.counter(
    "cim_search_rungs_total",
    "Portfolio race rungs / bandit waves executed", ("allocator",))
# continuous-batching scheduler families (docs/scheduler.md); the queue
# owns the admission counters, the engine owns the budget-flow ones
_M_SCHED_RELEASED = _REG.counter(
    "cim_sched_budget_released_pulls_total",
    "Race pulls released into the shared pool by flatlined jobs")
_M_SCHED_ABSORBED = _REG.counter(
    "cim_sched_budget_absorbed_pulls_total",
    "Shared-pool race pulls absorbed by still-improving jobs")
_M_SCHED_FLATLINED = _REG.counter(
    "cim_sched_flatlined_jobs_total",
    "Jobs whose bandit improvement rate flatlined mid-race")
for _m in (_M_SCHED_RELEASED, _M_SCHED_ABSORBED, _M_SCHED_FLATLINED):
    _m.inc(0)              # eager child: families render even when idle


# --------------------------------------------------------------------- #
# persistent (cross-process) compilation cache
# --------------------------------------------------------------------- #
_persistent_cache_dir: str | None = None

#: the cache directory when ``JAX_COMPILATION_CACHE_DIR`` is not set: a
#: fixed path inside the checkout, so every process of one command (and
#: the next command on the same checkout) shares it
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax-compile-cache")


def enable_persistent_compilation_cache() -> str | None:
    """Switch on JAX's persistent compilation cache for this process.

    On by default for every :class:`ExplorationEngine` so benchmark and CI
    processes reuse each other's compiles.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, and nothing
    is set here; otherwise the cache lives at :data:`CHECKOUT_CACHE_DIR`.
    ``CIM_TUNER_DISABLE_PERSISTENT_CACHE=1`` opts out.  Returns the active
    cache directory (``None`` when disabled or when the directory cannot
    be used, which is logged).
    """
    global _persistent_cache_dir
    if os.environ.get("CIM_TUNER_DISABLE_PERSISTENT_CACHE"):
        return None
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        _persistent_cache_dir = jax.config.jax_compilation_cache_dir
        return _persistent_cache_dir
    if jax.config.jax_compilation_cache_dir == CHECKOUT_CACHE_DIR:
        return _persistent_cache_dir
    try:
        os.makedirs(CHECKOUT_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
        # our SA executables compile in O(1s); make sure they qualify
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
        # JAX latches "cache disabled" at its FIRST compile (tiny ops fire
        # during import, before this config lands); reset so the next
        # compile re-initializes against the directory we just set
        from jax.experimental.compilation_cache import (
            compilation_cache as jax_cc,
        )
        jax_cc.reset_cache()
    except OSError as exc:
        _LOG.warning("persistent compilation cache off: cannot use %s: %s",
                     CHECKOUT_CACHE_DIR, exc)
        return None
    _persistent_cache_dir = CHECKOUT_CACHE_DIR
    return _persistent_cache_dir


# --------------------------------------------------------------------- #
# job description + result
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class ExploreJob:
    """One (macro, workload, objective, strategy set, area budget) job."""

    macro: MacroSpec
    workload: Workload
    area_budget_mm2: float
    objective: str = "ee"
    strategy_set: str = "st"
    bw: int = 256
    tech: TechConstants = DEFAULT_TECH
    space: DesignSpace | None = None
    merge_ops: bool = True
    #: search backend used when ``run(method=None)`` -- any registered
    #: ``repro.search`` backend name, or "exhaustive"
    search_method: str = "sa"
    #: optional per-job backend settings (the backend's settings
    #: dataclass, e.g. ``GASettings``); ``None`` means the backend's
    #: defaults.  Used when ``run(settings=None)`` and the type matches
    #: the effective method's settings class, so one batch may mix
    #: settings (each (bucket, method, settings) group is one jitted
    #: call).  Folds into :func:`job_key` exactly like an explicit
    #: ``settings=`` would.
    search_settings: typing.Any = None

    def merged_workload(self) -> Workload:
        """The operator list actually evaluated (merged unless opted out)."""
        return self.workload.merged() if self.merge_ops else self.workload

    def design_space(self) -> DesignSpace:
        """This job's axis space (the default space when none was given)."""
        return self.space or DesignSpace()


@dataclasses.dataclass
class ExploreResult:
    """One job's answer: the winning config, metrics, and search record."""

    config: AcceleratorConfig
    macro: MacroSpec
    workload: str
    objective: str
    strategy_set: str
    per_op_strategy: dict[str, str]
    metrics: dict
    search: dict                      # method, runtime, space stats
    #: per-member diagnostics of the stochastic backend run (named ``sa``
    #: for historical reasons; carries any backend's SearchResult)
    sa: SearchResult | None = None

    def summary(self) -> str:
        """One-line human-readable row (what the CLI/benchmarks print)."""
        c = self.config
        return (
            f"[{self.workload} | {self.macro.name} | {self.objective}/"
            f"{self.strategy_set}] (MR,MC,SCR,IS,OS)="
            f"({c.mr},{c.mc},{c.scr},{c.is_kb},{c.os_kb}) "
            f"EE={self.metrics['tops_w']:.2f} TOPS/W "
            f"Th={self.metrics['gops']:.1f} GOPS "
            f"area={self.metrics['area_mm2']:.2f} mm^2"
        )


# --------------------------------------------------------------------- #
# canonical job identity (dedup + the service result store)
# --------------------------------------------------------------------- #
#: bump when the cost model / result schema changes meaning, so persisted
#: results keyed under the old schema stop matching.  Schema 2 folded
#: (search method, backend settings) into the key for EVERY backend, so a
#: warm-store SA result can never be returned for a GA/DE/Sobol/portfolio
#: query (or vice versa).  Schema 3: ``ExploreJob.search_settings`` joined
#: the job dataclass; it is normalized OUT of the job's canonical form and
#: hashed through the key's single ``settings`` slot instead, so the
#: "settings on the job" and "settings as an argument" spellings of one
#: exploration share a key.  Schema 4: a ``calibration`` slot joined the
#: payload -- the active calibration version when the settings request
#: measured fidelity, ``None`` otherwise -- so warm analytic results can
#: never answer calibrated queries (and a re-fit calibration can never be
#: answered by a stale measured result).  Schema 5: ``PortfolioSettings``
#: grew the budget-flow / device-affinity knobs (``flatline_waves``,
#: ``flatline_eps``, ``device_affinity``); they hash through the
#: ``settings`` slot, and the explicit bump retires every pre-scheduler
#: stored result at once instead of only the portfolio ones.
JOB_KEY_SCHEMA = 5


def valid_methods() -> tuple[str, ...]:
    """Every accepted ``method=`` name: the registered ``repro.search``
    backends plus the pruned-space ``"exhaustive"`` sweep."""
    return available_backends() + ("exhaustive",)


def _check_method(method: str) -> None:
    if method != "exhaustive":
        get_backend(method)              # raises ValueError with the list


def preferred_settings(job: "ExploreJob | None", method: str,
                       settings=None):
    """THE settings-precedence rule, in one place: explicit ``settings``
    wins, then a type-matching ``job.search_settings``, else ``None``
    (the caller applies its own default resolution).  Shared by
    :func:`job_key`, :meth:`ExplorationEngine._effective_settings` and
    ``repro.service.queue.resolve_settings`` so the canonical key
    computed at submit time can never diverge from the settings a job
    actually runs with."""
    if method == "exhaustive":
        return None
    if settings is not None:
        return settings
    s = job.search_settings if job is not None else None
    if s is not None and isinstance(s, get_backend(method).settings_cls):
        return s
    return None


def _canonical(obj):
    """JSON-able canonical form of job ingredients (dataclasses, tuples,
    floats-as-hex so equality is bit-exact, not repr-approximate)."""
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        out = {"__type__": type(obj).__name__}
        for f in dataclasses.fields(obj):
            out[f.name] = _canonical(getattr(obj, f.name))
        return out
    if isinstance(obj, (tuple, list)):
        return [_canonical(x) for x in obj]
    if isinstance(obj, dict):
        return {str(k): _canonical(v) for k, v in sorted(obj.items())}
    if isinstance(obj, (bool, np.bool_)):
        return bool(obj)
    if isinstance(obj, (int, np.integer)):
        return int(obj)
    if isinstance(obj, (float, np.floating)):
        return float(obj).hex()
    if isinstance(obj, str) or obj is None:
        return obj
    return repr(obj)                               # pragma: no cover


def job_key(
    job: ExploreJob,
    method: str | None = None,
    settings=None,
) -> str:
    """Content hash identifying one exploration's *answer*.

    Two submissions share a key iff they are guaranteed to produce
    bit-identical results: same job ingredients (macro, workload, budget,
    objective, strategy set, bandwidth, tech constants, design space,
    merge flag), same search method (``None`` defers to
    ``job.search_method``), same backend settings when the method is a
    search backend (``None`` defers to a type-matching
    ``job.search_settings``), and the same x64 mode.  Callers that resolve
    backend *defaults* (the queue, the engine) must pass the resolved
    settings so defaulted and explicit spellings share a key.  Used for
    in-batch dedup (:meth:`ExplorationEngine.run`), in-flight dedup in the
    service queue, and as the content address of the persistent result
    store.
    """
    method = method or job.search_method
    settings = preferred_settings(job, method, settings)
    calibration = None
    if getattr(settings, "fidelity", "analytic") == "measured":
        from repro.core.calibration import active_calibration_version
        calibration = active_calibration_version()
    payload = {
        "schema": JOB_KEY_SCHEMA,
        "calibration": calibration,
        # normalize search_method into the job (so "method override" and
        # "job field" spellings of the same exploration share a key) and
        # search_settings OUT of it (hashed via the "settings" slot below,
        # so the job-field and argument spellings share a key too)
        "job": _canonical(dataclasses.replace(
            job, space=job.design_space(), search_method=method,
            search_settings=None)),
        "method": method,
        "settings": _canonical(settings) if method != "exhaustive" else None,
        "x64": bool(jax.config.jax_enable_x64),
    }
    blob = json.dumps(payload, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()


class _PreparedJob(typing.NamedTuple):
    job: ExploreJob
    workload: Workload               # merged view actually evaluated
    ops_pad: int                     # operator bucket width
    mat: np.ndarray                  # [5, L] axis-value matrix (unpadded L)
    lens: np.ndarray                 # [5]
    key: str | None = None           # canonical job key (span ``job`` arg)


def _phase(name: str, **args):
    """A span of one engine phase, observed in ``cim_engine_phase_seconds``
    under ``phase=name``."""
    return obs.span(f"engine.{name}", histogram=_PHASES[name], **args)


def _fallback_arg(fallback: bool) -> dict:
    """The span arg that marks a phase run by a snap fallback."""
    return {"fallback": True} if fallback else {}


#: traces made on this thread so far; an executable call that raised it
#: re-traced (the counter is per thread, so concurrent engines never
#: claim one another's traces)
_THREAD_TRACES = threading.local()


def _note_trace(executable: str) -> None:
    """Called from an executable's traced Python body, which runs once
    per trace (a new shape such as a new jobs-per-dispatch count, and
    again when the persistent compile cache then serves the compile)."""
    _M_TRACES.inc(executable=executable)
    _THREAD_TRACES.n = getattr(_THREAD_TRACES, "n", 0) + 1


def _count_traces(fn, executable: str):
    """Wrap a jitted executable so each call that re-traced is recorded
    as an ``engine.compile`` span (args: executable, J) and a
    ``cim_engine_compile_seconds`` observation; other calls pass
    through."""
    def wrapper(stacked, *a):
        before = getattr(_THREAD_TRACES, "n", 0)
        t0 = time.perf_counter()
        out = fn(stacked, *a)
        if getattr(_THREAD_TRACES, "n", 0) != before:
            dt = time.perf_counter() - t0
            jobs = int(np.shape(jax.tree.leaves(stacked)[0])[0])
            obs.record("engine.compile", t0, dt, histogram=_M_COMPILE_S,
                       executable=executable, J=jobs)
            _LOG.debug("traced %s at J=%d in %.2fs", executable, jobs, dt)
        return out

    wrapper.__wrapped__ = fn         # the jitted callable (``.lower``)
    return wrapper


def _pow2_at_least(n: int, floor: int = 4) -> int:
    return max(floor, 1 << (int(n) - 1).bit_length())


def _job_arrays(p: _PreparedJob) -> cost_model.JobParams:
    """Numpy-leaved JobParams for one prepared job (stacked by the caller)."""
    j = p.job
    return cost_model.JobParams(
        ops=p.workload.as_arrays(pad_to=p.ops_pad),
        macro=cost_model.MacroParams(*[
            np.float64(v)
            for v in cost_model.macro_params(j.macro, j.tech)]),
        tech=cost_model.TechParams(*[
            np.float64(v) for v in cost_model.tech_params(j.tech)]),
        allowed=cost_model.strategy_mask(j.strategy_set),
        obj_code=np.int32(cost_model.objective_code(j.objective)),
        area_budget=np.float64(j.area_budget_mm2),
        bw=np.float64(j.bw),
    )


def _stack_jobs(rows: list[cost_model.JobParams]) -> cost_model.JobParams:
    return jax.tree.map(lambda *xs: np.stack(xs), *rows)


#: widths of a :func:`_finish_row` after its operators: macro and tech
#: constants, strategy mask, objective code, config row
_FINISH_TAIL = (len(cost_model.MacroParams._fields),
                len(cost_model.TechParams._fields), len(ALL_STRATEGIES), 1, 6)


def _finish_row(p: _PreparedJob, cfg: AcceleratorConfig) -> np.ndarray:
    """One job's epilogue inputs as one ``[1, 5 * ops_pad + 38]`` float64
    row (operators, then :data:`_FINISH_TAIL`), so the call transfers one
    array to the device and not one per leaf."""
    j = _job_arrays(p)
    return np.concatenate([
        j.ops.ravel(), j.macro, j.tech, j.allowed, [j.obj_code],
        [cfg.mr, cfg.mc, cfg.scr, cfg.is_kb, cfg.os_kb, cfg.bw]])[None]


def _spearman(a: np.ndarray, b: np.ndarray) -> float:
    """Spearman rank correlation between two value vectors (1.0 for
    degenerate inputs: fewer than two points, or zero rank variance).
    The two-fidelity report uses it to quantify how well the analytic
    ranking predicted the measured one."""
    a = np.asarray(a, dtype=float)
    b = np.asarray(b, dtype=float)
    if len(a) < 2:
        return 1.0
    ra = np.argsort(np.argsort(a, kind="stable"),
                    kind="stable").astype(float)
    rb = np.argsort(np.argsort(b, kind="stable"),
                    kind="stable").astype(float)
    da, db = ra - ra.mean(), rb - rb.mean()
    denom = float(np.sqrt((da ** 2).sum() * (db ** 2).sum()))
    if denom == 0.0:                                   # pragma: no cover
        return 1.0
    return float((da * db).sum() / denom)


def clone_result(r: ExploreResult) -> ExploreResult:
    """Fan-out copy for deduped submissions (fresh mutable containers so
    callers mutating one result cannot alias another).  ``search`` is
    deep-copied: portfolio results nest mutable dicts inside it."""
    return dataclasses.replace(
        r, per_op_strategy=dict(r.per_op_strategy),
        metrics=dict(r.metrics), search=copy.deepcopy(r.search))


# --------------------------------------------------------------------- #
# the engine
# --------------------------------------------------------------------- #
class ExplorationEngine:
    """Runs lists of :class:`ExploreJob` through shared jitted executables.

    One engine instance owns one executable cache; the process-wide
    :func:`default_engine` is shared by the ``co_explore`` family so
    interleaved single-job calls amortize compiles too.  Set
    ``executable_cache=False`` to measure the seed repo's retrace-per-job
    behaviour (the benchmark's "sequential" leg).
    """

    #: candidate block width of the exhaustive executable; every chunked
    #: call shares one compiled signature regardless of candidate count
    EXHAUSTIVE_CHUNK = 4096

    def __init__(
        self,
        sa_settings: SASettings = SASettings(),
        executable_cache: bool = True,
        persistent_compile_cache: bool = True,
        penalty_scale: float = 1e3,
        device_race: bool = True,
    ):
        """Build an engine (one executable cache, optional device racing).

        ``sa_settings`` are the defaults the ``"sa"`` method runs with;
        ``executable_cache=False`` disables the in-process executable
        cache (the benchmark's retrace-per-job "sequential" leg);
        ``device_race=False`` pins portfolio races to the default device
        even when more are visible.
        """
        self.sa_settings = sa_settings
        self.penalty_scale = float(penalty_scale)
        self._use_cache = bool(executable_cache)
        self._device_race = bool(device_race)
        self._executables: dict = {}
        # legacy-shaped per-instance counters mirrored into the
        # process-wide registry (the /v1/metrics families above)
        self.stats = obs.StatCounters({
            "jobs": _M_JOBS.labels(),
            "batches": _M_BATCHES.labels(),
            "dedup_hits": _M_DEDUP.labels(),
            "executable_cache_hits": _M_EXEC.labels(outcome="hit"),
            "executable_cache_misses": _M_EXEC.labels(outcome="miss"),
            "device_race_dispatches": _M_RACE.labels(),
        })
        #: device_race_dispatches per device the dispatch ran on
        self.race_dispatch_devices: collections.Counter = \
            collections.Counter()
        if persistent_compile_cache:
            enable_persistent_compilation_cache()

    def stats_snapshot(self) -> dict:
        """JSON-able counter view for service introspection (``/v1/stats``):
        the run counters plus the live executable-cache size and the active
        persistent compile-cache directory."""
        return {
            **self.stats.snapshot(),
            "executable_cache_size": len(self._executables),
            "persistent_compile_cache": _persistent_cache_dir,
            "device_race_dispatches_by_device":
                dict(self.race_dispatch_devices),
        }

    # ------------------------------------------------------------- #
    # executable cache
    # ------------------------------------------------------------- #
    def _cached(self, key, build):
        """The executable cached under ``key``, else ``build()``'s
        ``(name, jitted)`` wrapped by :func:`_count_traces`."""
        hit = self._use_cache and key in self._executables
        self.stats.bump("executable_cache_hits" if hit else
                        "executable_cache_misses")
        if hit:
            return self._executables[key]
        name, fn = build()
        wrapped = _count_traces(fn, name)
        if self._use_cache:
            self._executables[key] = wrapped
        return wrapped

    def _search_executable(self, backend, ops_pad: int, axes_pad: int,
                           settings):
        """One jitted vmapped executable per (backend, bucket, settings) --
        every ``repro.search`` backend shares this path, so a GA sweep and
        an SA sweep over the same bucket are two cache entries, each
        compiled once.  For backends honouring the ``seed_free_run``
        contract (all randomness enters via the ``keys`` argument) the RNG
        seed is normalized out of the cache key, so reseeded runs
        (hypothesis sweeps, portfolio rungs) share one compile; backends
        that read ``settings.seed`` inside ``run`` keep the seed in the
        key and compile per seed."""
        cache_settings = settings
        if backend.seed_free_run:
            try:
                cache_settings = dataclasses.replace(settings, seed=0)
            except TypeError:                          # seedless settings
                pass
        key = (backend.name, ops_pad, axes_pad, cache_settings,
               bool(jax.config.jax_enable_x64))

        name = f"one_job_{backend.name}"

        def build():
            def one_job(job, mat, lens, keys):
                _note_trace(name)

                def objective(cfg_row):
                    return cost_model.job_objective(
                        job, cfg_row, self.penalty_scale)
                return backend.run(objective, mat, lens, job.bw, settings,
                                   keys)
            # the name the device trace's module carries: jit_<name>
            one_job.__name__ = one_job.__qualname__ = name
            return name, jax.jit(jax.vmap(one_job))

        return self._cached(key, build)

    def _exhaustive_executable(self, ops_pad: int):
        key = ("exhaustive", ops_pad, self.EXHAUSTIVE_CHUNK,
               bool(jax.config.jax_enable_x64))

        def build():
            def one_job_sweep(job, cand_block):
                _note_trace("one_job_sweep")

                def objective(cfg_row):
                    return cost_model.job_objective(
                        job, cfg_row, self.penalty_scale)
                return jax.vmap(objective)(cand_block)
            return "one_job_sweep", jax.jit(jax.vmap(one_job_sweep))

        return self._cached(key, build)

    def _finish_executable(self, ops_pad: int):
        """The result epilogue of one operator bucket width: the delivered
        config's totals, area and per-op strategies of one job
        (:func:`cost_model.workload_metrics_core`), read from one
        :func:`_finish_row` and returned as one vector (``[lat, en, area,
        true_ops, strategy_idx...]``), so a call moves one array each way.
        Vmapped over a one-job stack, the compile depends on ``ops_pad``
        alone."""
        key = ("finish", ops_pad, bool(jax.config.jax_enable_x64))
        cuts = np.cumsum(_FINISH_TAIL)[:-1]

        def build():
            def finish_metrics(row):
                _note_trace("finish_metrics")
                ops = row[:5 * ops_pad].reshape(ops_pad, 5)
                macro, tech, allowed, code, cfg_row = jnp.split(
                    row[5 * ops_pad:], cuts)
                job = cost_model.JobParams(
                    ops=ops, macro=cost_model.MacroParams(*macro),
                    tech=cost_model.TechParams(*tech), allowed=allowed,
                    obj_code=code[0].astype(jnp.int32), area_budget=jnp.inf,
                    bw=cfg_row[5])
                lat, en, idx, area, true_ops = \
                    cost_model.workload_metrics_core(job, cfg_row)
                return jnp.concatenate([jnp.stack([lat, en, area, true_ops]),
                                        idx.astype(lat.dtype)])
            return "finish_metrics", jax.jit(jax.vmap(finish_metrics))

        return self._cached(key, build)

    # ------------------------------------------------------------- #
    # public API
    # ------------------------------------------------------------- #
    def default_settings(self, method: str):
        """Effective settings when the caller supplies none: the engine's
        construction-time ``sa_settings`` for SA (back-compat), the
        backend's defaults otherwise, ``None`` for exhaustive."""
        if method == "exhaustive":
            return None
        if method == "sa":
            return self.sa_settings
        return get_backend(method).default_settings()

    def _resolve_settings(self, method: str, settings):
        if method == "exhaustive":
            return None                # sweep has no knobs; ignore settings
        if settings is None:
            return self.default_settings(method)
        backend = get_backend(method)
        if not isinstance(settings, backend.settings_cls):
            raise TypeError(
                f"method {method!r} expects {backend.settings_cls.__name__}"
                f" settings, got {type(settings).__name__}")
        return settings

    def _effective_settings(self, job: ExploreJob, method: str, settings):
        """The settings one job actually runs with: the shared
        :func:`preferred_settings` precedence (explicit > type-matching
        ``job.search_settings``), then this engine's defaults.  A type
        MISmatch -- job settings left over from a different
        ``search_method`` under a ``method=`` override -- silently falls
        back to defaults."""
        if settings is not None:
            return self._resolve_settings(method, settings)  # type-check
        s = preferred_settings(job, method)
        return s if s is not None else self.default_settings(method)

    def run(
        self,
        jobs: typing.Sequence[ExploreJob],
        method: str | None = None,
        settings=None,
        sa_settings: SASettings | None = None,
        keys: typing.Sequence[str] | None = None,
        admit: typing.Callable[[], list] | None = None,
    ) -> list[ExploreResult]:
        """Co-explore every job; results come back in submission order.

        ``method`` is any registered ``repro.search`` backend name
        (``"sa"``, ``"genetic"``, ``"evolution"``, ``"sobol"``,
        ``"portfolio"``, ...) or ``"exhaustive"``; ``None`` uses each
        job's own ``search_method``, so one batch may mix methods (each
        (method, shape bucket, settings) group runs as one jitted call).
        ``settings`` must match the backend's settings class, requires a
        homogeneous method across the batch, and overrides every job's
        own ``search_settings``; with ``settings=None`` each job runs
        with its ``search_settings`` (backend defaults when unset), so
        one batch may also mix settings -- e.g. bandit- and
        halving-allocator portfolios side by side.  ``sa_settings`` is
        the legacy alias.  ``keys`` lets callers that already computed
        :func:`job_key` for each job (the service queue) skip re-hashing;
        when given it must align 1:1 with ``jobs``.

        ``admit`` is the continuous-batching admission hook (see
        docs/scheduler.md): a callable polled once per bandit wave that
        returns late-arriving ``(job, key)`` pairs to join the in-flight
        race at the next rung boundary.  It requires a single-bucket
        batch running a bandit-allocator portfolio (the only phase
        structure with rung boundaries that keeps per-job schedules
        independent); admitted jobs start their own pull schedule from
        zero, so each one's result is bit-identical to a solo
        submission.  Their results are appended AFTER the initial jobs'
        results, in admission order.
        """
        with obs.span("engine.run", histogram=_M_RUN_S,
                      jobs=len(jobs)) as sp:
            return self._run(jobs, method, settings, sa_settings, keys,
                             admit, sp)

    def _run(self, jobs, method, settings, sa_settings, keys, admit,
             sp: obs.Span) -> list[ExploreResult]:
        """:meth:`run` inside its ``engine.run`` span."""
        t_start = sp.t0
        if settings is None:
            settings = sa_settings
        methods = [method or j.search_method for j in jobs]
        for m in set(methods):
            _check_method(m)
        if settings is not None and len(set(methods)) > 1:
            raise ValueError(
                "explicit settings require a single method across the "
                f"batch, got {sorted(set(methods))}")
        eff = [self._effective_settings(j, m, settings)
               for j, m in zip(jobs, methods)]

        # identical submissions (same canonical key) evaluate ONCE; the
        # result fans out to every duplicate slot below
        if keys is None:
            keys = [job_key(j, m, s)
                    for j, m, s in zip(jobs, methods, eff)]
        elif len(keys) != len(jobs):
            raise ValueError(
                f"keys length {len(keys)} != jobs length {len(jobs)}")
        first_of: dict[str, int] = {}
        unique: list[int] = []
        for i, k in enumerate(keys):
            if k in first_of:
                self.stats.bump("dedup_hits")
            else:
                first_of[k] = i
                unique.append(i)

        sp.set(unique=len(unique))
        with _phase("prepare", jobs=len(unique)):
            prepared = {i: self._prepare(jobs[i])._replace(key=keys[i])
                        for i in unique}
        self.stats.bump("jobs", len(jobs))

        results: list[ExploreResult | None] = [None] * len(jobs)
        admitted_results: list[ExploreResult] = []
        bucket_groups = self._buckets(
            [(i, prepared[i]) for i in unique], methods, eff)
        if admit is not None:
            self._check_admittable(bucket_groups)
        for (bucket, group_settings), members in bucket_groups.items():
            m = bucket[0]
            idxs = [i for i, _ in members]
            batch = [p for _, p in members]
            self.stats.bump("batches")
            _LOG.debug("batch method=%s jobs=%d bucket=%s",
                       m, len(idxs), bucket)
            with obs.span("engine.batch", method=m, jobs=len(idxs),
                          bucket=str(bucket)):
                if m == "exhaustive":
                    outs = self._run_exhaustive_batch(batch)
                else:
                    backend = get_backend(m)
                    if backend.composite:
                        outs = self._run_portfolio_batch(
                            batch, group_settings,
                            job_keys=[keys[i] for i in idxs],
                            admit=None if admit is None else
                            self._wrap_admit(admit, bucket, m))
                        # rung-admitted jobs ride behind the initial
                        # batch; their results resolve positionally
                        # after every submitted job's
                        admitted_results = list(outs[len(idxs):])
                        outs = outs[:len(idxs)]
                    else:
                        outs = self._run_search_batch(batch, backend,
                                                      group_settings)
            for i, out in zip(idxs, outs):
                results[i] = out
        fanout: dict[str, int] = {}
        for i, k in enumerate(keys):
            if results[i] is None:
                results[i] = clone_result(results[first_of[k]])
                fanout[k] = fanout.get(k, 0) + 1
        # dedup provenance: a timeline whose result fanned out to
        # duplicate slots says so (annotate no-ops for keys without one)
        recorder = obs.flight_recorder()
        for k, n in fanout.items():
            recorder.annotate(k, dedup_fanout=n)

        results.extend(admitted_results)
        runtime = time.perf_counter() - t_start
        for r in results:
            r.search["runtime_s"] = runtime
            r.search["batch_jobs"] = len(results)
        return typing.cast("list[ExploreResult]", results)

    def candidate_values(
        self,
        jobs: typing.Sequence[ExploreJob],
        candidates: typing.Sequence[np.ndarray],
    ) -> list[np.ndarray]:
        """Objective values of explicit candidate lists, one ``[C_j]`` float
        array per job (batched across jobs; used by the Pareto frontier)."""
        prepared = [self._prepare(j) for j in jobs]
        out: list[np.ndarray | None] = [None] * len(prepared)
        groups: dict = {}
        for i, p in enumerate(prepared):
            groups.setdefault(p.ops_pad, []).append(i)
        for ops_pad, idxs in groups.items():
            stacked = _stack_jobs([_job_arrays(prepared[i]) for i in idxs])
            vals = self._sweep_values(
                ops_pad, stacked, [np.asarray(candidates[i], np.float64)
                                   for i in idxs])
            for i, v in zip(idxs, vals):
                out[i] = v
        return typing.cast("list[np.ndarray]", out)

    # ------------------------------------------------------------- #
    # internals
    # ------------------------------------------------------------- #
    def _prepare(self, job: ExploreJob) -> _PreparedJob:
        wl = job.merged_workload()
        mat, lens = _axes_matrix(job.design_space())
        return _PreparedJob(
            job=job, workload=wl,
            ops_pad=_pow2_at_least(len(wl.ops)),
            mat=mat, lens=lens,
        )

    def bucket_key(self, job: ExploreJob, method: str | None = None) -> tuple:
        """Executable-signature bucket of a job: jobs sharing a bucket run
        in one batched call (the service queue groups submissions by this
        so each micro-batch dispatches as exactly one ``run()``)."""
        method = method or job.search_method
        with _phase("bucket"):
            p = self._prepare(job)
        return self._bucket_key(p, method)

    @staticmethod
    def _bucket_key(p: _PreparedJob, method: str) -> tuple:
        if method == "exhaustive":
            return ("exhaustive", p.ops_pad)
        return (method, p.ops_pad, _pow2_at_least(p.mat.shape[1]))

    def _buckets(
        self, prepared: list[tuple[int, _PreparedJob]],
        methods: typing.Sequence[str],
        eff: typing.Sequence,
    ) -> dict:
        """Group (index, prepared) pairs by (executable signature,
        effective settings), preserving order -- jobs only share a batched
        call when both their compiled signature AND their resolved
        settings agree (settings dataclasses are frozen, hence hashable).
        """
        groups: dict = {}
        for i, p in prepared:
            key = (self._bucket_key(p, methods[i]), eff[i])
            groups.setdefault(key, []).append((i, p))
        return groups

    # ---- continuous-batching admission (docs/scheduler.md) -------- #
    @staticmethod
    def _check_admittable(bucket_groups: dict) -> None:
        """Reject ``admit=`` for batches that have no rung boundaries to
        admit at: admission needs exactly one executable bucket, running
        the composite portfolio under the bandit allocator (halving
        culls across rungs and plain backends are single-shot, so a
        late join would perturb the in-flight jobs)."""
        if len(bucket_groups) != 1:
            raise ValueError(
                "rung admission requires a single executable bucket per "
                f"run() call, got {len(bucket_groups)} groups")
        ((bucket, group_settings),) = bucket_groups.keys()
        m = bucket[0]
        if m == "exhaustive" or not get_backend(m).composite or \
                getattr(group_settings, "allocator", None) != "bandit":
            raise ValueError(
                "rung admission requires a bandit-allocator portfolio "
                f"group, got method={m!r} allocator="
                f"{getattr(group_settings, 'allocator', None)!r}")

    def _wrap_admit(self, admit, bucket: tuple, method: str):
        """Engine-side admission shim: prepares each late ``(job, key)``
        pair the caller's hook returns and verifies it really belongs to
        the in-flight executable bucket (the queue only offers
        compatible entries; a mismatch is a programming error that would
        silently corrupt the batched launch shapes)."""
        def engine_admit() -> list:
            out = []
            for job, key in admit():
                with _phase("prepare", job=key):
                    p = self._prepare(job)._replace(key=key)
                got = self._bucket_key(p, method)
                if got != bucket:
                    raise ValueError(
                        f"admitted job bucket {got} does not match the "
                        f"in-flight group bucket {bucket}")
                self.stats.bump("jobs")
                out.append((key, p))
            return out
        return engine_admit

    # ---- pluggable search-backend path ---------------------------- #
    def _dispatch_backend_async(
        self, batch: list[_PreparedJob], backend, settings,
        device=None, seed_rows: typing.Sequence[int] | None = None,
    ):
        """One batched backend call over a shape bucket, dispatched
        asynchronously (the returned triple holds live JAX arrays; JAX's
        async dispatch lets the portfolio launch several backends --
        possibly on several devices -- before blocking on any of them).

        ``device`` commits every operand to that device before the call,
        so the jitted executable runs there (``None`` = default
        placement); ``seed_rows`` supplies one RNG seed per job (the
        bandit allocator's per-job pull counters diverge, so one settings
        object can carry several jobs' seeds).
        """
        axes_pad = _pow2_at_least(max(p.mat.shape[1] for p in batch))
        stacked = _stack_jobs([_job_arrays(p) for p in batch])
        mats = np.stack([
            np.concatenate(
                [p.mat, np.repeat(p.mat[:, -1:], axes_pad - p.mat.shape[1],
                                  axis=1)], axis=1)
            for p in batch])                                 # [J, 5, L]
        lens = np.stack([p.lens for p in batch])             # [J, 5]
        if seed_rows is None:
            keys = np.stack([
                np.asarray(backend.make_keys(settings)) for _ in batch])
        else:
            keys = np.stack([
                np.asarray(backend.make_keys(
                    settings, key=jax.random.PRNGKey(int(s))))
                for s in seed_rows])

        fn = self._search_executable(
            backend, batch[0].ops_pad, axes_pad, settings)
        operands = (stacked, jnp.asarray(mats), jnp.asarray(lens),
                    jnp.asarray(keys))
        if device is None:
            return fn(*operands)
        out = fn(*jax.device_put(operands, device))
        self.stats.bump("device_race_dispatches")
        # where the executable really ran: the device its outputs live on
        for d in out[0].devices():
            self.race_dispatch_devices[str(d)] += 1
        return out

    def _dispatch_backend(
        self, batch: list[_PreparedJob], backend, settings,
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """One batched backend call over a shape bucket.  Returns numpy
        ``(best_idx [J, members, 5], best_val [J, members],
        trace [J, steps])``."""
        with _phase("executable", jobs=len(batch)):
            best_idx, best_val, trace = self._dispatch_backend_async(
                batch, backend, settings)
            return (np.asarray(best_idx), np.asarray(best_val),
                    np.asarray(trace))

    def _wrap_search_winner(
        self, p: _PreparedJob, method: str,
        best_idx: np.ndarray,          # [members, 5] of this job
        best_val: np.ndarray,          # [members]
        trace: np.ndarray,             # [steps]
    ) -> ExploreResult:
        """Shared epilogue of every stochastic backend: pick the winning
        member, snap-verify the area budget, attach diagnostics.  The
        ``engine.finish`` phase covers all of it but a snap fallback,
        which runs first, under its own ``engine.fallback`` span."""
        job = p.job
        winner = int(np.argmin(best_val))
        vals = p.mat[np.arange(5), best_idx[winner]]
        cfg = AcceleratorConfig(
            *[int(round(v)) for v in vals], bw=job.bw)
        search: dict = {"method": method,
                        "merged_ops": len(p.workload.ops),
                        "raw_ops": len(job.workload.ops)}
        # backends walk the raw grid with an area penalty; snap-verify
        # feasibility and fall back to the pruned-space optimum if the
        # penalty let the winner out of budget (rare)
        if accelerator_area_mm2(cfg, job.macro, job.tech) > \
                job.area_budget_mm2 * 1.001:
            with obs.span("engine.fallback", job=p.key):
                cfg, stats = self._exhaustive_one(p)
            search.update(stats)
        with _phase("finish", job=p.key):
            diag = SearchResult(
                best_cfg=jnp.asarray(
                    np.concatenate([vals, [float(job.bw)]])),
                best_value=jnp.asarray(best_val[winner]),
                best_per_chain=jnp.asarray(best_val),
                trace_best=jnp.asarray(trace),
            )
            return self._finish(p, cfg, search, diag)

    def _run_search_batch(
        self, batch: list[_PreparedJob], backend, settings,
    ) -> list[ExploreResult]:
        best_idx, best_val, trace = self._dispatch_backend(
            batch, backend, settings)
        return [
            self._wrap_search_winner(
                p, backend.name, best_idx[jx], best_val[jx], trace[jx])
            for jx, p in enumerate(batch)
        ]

    # ---- portfolio (bandit / successive-halving racer) ------------ #
    def _race_devices(self) -> list:
        """Devices portfolio race waves round-robin across.  ``[None]``
        (default placement, no transfer) when only one device is visible
        or ``device_race=False`` -- the single-device fallback is the same
        code path with no placement step."""
        if not self._device_race:
            return [None]
        from repro.core.distributed import race_devices

        devs = race_devices()
        return list(devs) if len(devs) > 1 else [None]

    def _run_portfolio_batch(
        self, batch: list[_PreparedJob], settings,
        job_keys: typing.Sequence[str] | None = None,
        admit: typing.Callable[[], list] | None = None,
    ) -> list[ExploreResult]:
        """Race the constituent backends per job under the settings'
        budget allocator, then spend the remaining budget on each job's
        winner.  The reported best is the min across every phase.
        ``job_keys`` (aligned 1:1 with ``batch``) enables per-rung
        progress events on :func:`repro.obs.progress_bus` -- one event
        per job per race wave plus a ``phase="final"`` event -- so SSE
        clients watch the race converge.

        ``allocator="bandit"``: after one initialization pull per backend
        (identical to halving's rung 0), each adaptive pull goes to the
        per-job UCB argmax over observed improvement rates -- rewards come
        from the best-so-far traces the runs already return, so the
        schedule is bit-deterministic given the seed.
        ``allocator="halving"``: fixed rungs, per-job culling to the best
        ``ceil(k/2)`` each rung.

        The bandit race runs as a continuous-batching wave scheduler
        (docs/scheduler.md): every bandit state (pull counters, rewards,
        UCB choice, derived seeds) is per-job, so the wave loop carries
        each job through its OWN schedule and two extensions fall out
        without perturbing anyone's trajectory:

        * ``admit`` -- prepared late jobs returned by the hook (see
          :meth:`run`) join the next wave at pull 0 and race to
          completion inside this call; with no arrivals the loop is
          bit-identical to the classic closed-batch race.
        * cross-job budget flow -- with ``settings.flatline_waves > 0``,
          a job whose last ``flatline_waves`` adaptive pulls each earned
          reward below ``flatline_eps`` releases its remaining race
          pulls into a shared pool that still-improving jobs drain one
          pull per wave; per-job accounting lands in
          ``search["budget_flow"]`` and as ``phase="budget_flow"``
          SSE/recorder events.

        Every wave's constituent runs are dispatched asynchronously and
        placed across the visible JAX devices (:meth:`_race_devices`;
        round-robin, or pinned per constituent via
        ``settings.device_affinity``); the fold of each wave's results
        into the per-job incumbents is the per-rung best exchange (the
        host-side analogue of ``core/distributed.py``'s ``pmin``
        collective).
        """
        from repro.search.portfolio import (
            bandit_pull_plan,
            bandit_rounds,
            constituent_devices,
            derived_seed,
            final_plan,
            pull_reward,
            race_plan,
            ucb_scores,
        )

        batch = list(batch)
        job_keys = None if job_keys is None else list(job_keys)
        if admit is not None and job_keys is None:
            raise ValueError("rung admission requires job_keys")
        names = settings.backends
        n_jobs, n_back = len(batch), len(names)
        devices = self._race_devices()
        n_devices = sum(d is not None for d in devices) or 1
        dev_of = constituent_devices(settings, devices)
        bus = obs.progress_bus()
        recorder = obs.flight_recorder()
        # the flight recorder opens one decision timeline per job,
        # capturing the same per-rung payloads the SSE bus publishes
        # (so the two reconcile exactly) plus bandit internals
        device_map = {name: str(dev_of[b_idx] or "default")
                      for b_idx, name in enumerate(names)}
        if job_keys is not None:
            for j in range(n_jobs):
                recorder.start(
                    job_keys[j], method="portfolio",
                    allocator=settings.allocator, backends=list(names),
                    devices=n_devices, device_map=device_map,
                    total_evals=settings.total_evals,
                    rungs=settings.rungs, seed=settings.seed)
        best_val = np.full(n_jobs, np.inf)
        best_idx = np.zeros((n_jobs, 5), dtype=np.int64)
        per_backend = np.full((n_jobs, n_back), np.inf)
        # diagnostics track the run that PRODUCED each job's current best,
        # so min(best_per_chain) == min(trace_best) == the reported value
        member_vals: list[np.ndarray | None] = [None] * n_jobs
        traces: list[np.ndarray | None] = [None] * n_jobs
        # per-job candidate pool across every phase (axis-index tuple ->
        # best analytic value seen); the measured fidelity's final phase
        # re-scores the top-K of this pool with calibrated constants
        pool: list[dict[tuple, float]] = [dict() for _ in range(n_jobs)]

        def _launch(b_idx: int, scaled, sel: list[int],
                    seed_rows=None):
            """Dispatch one backend's run over ``sel`` (async, possibly on
            a non-default device); returns a handle for :func:`_collect`.
            """
            if not sel:
                return None
            arrays = self._dispatch_backend_async(
                [batch[j] for j in sel], get_backend(names[b_idx]), scaled,
                device=dev_of[b_idx], seed_rows=seed_rows)
            return (b_idx, sel, arrays)

        def _collect(handle, prev=None,
                     fold_race=True) -> dict[int, tuple[float, float]]:
            """Block on one launched run and fold it into the per-job
            incumbents (the best exchange); returns ``{job: (run best,
            pull reward vs the pre-wave incumbents ``prev``)}``.  Only
            the bandit race passes ``prev`` -- the halving and final
            phases don't consume rewards, so none are computed."""
            b_idx, sel, (idx_a, val_a, tr_a) = handle
            idx_a, val_a, tr_a = (np.asarray(idx_a), np.asarray(val_a),
                                  np.asarray(tr_a))
            out: dict[int, tuple[float, float]] = {}
            for pos, j in enumerate(sel):
                w = int(np.argmin(val_a[pos]))
                v = float(val_a[pos, w])
                out[j] = (v, pull_reward(prev[j], tr_a[pos])
                          if prev is not None else 0.0)
                if fold_race:
                    per_backend[j, b_idx] = min(per_backend[j, b_idx], v)
                if v < best_val[j]:
                    best_val[j] = v
                    best_idx[j] = idx_a[pos, w]
                    member_vals[j] = val_a[pos]
                    traces[j] = tr_a[pos]
                pj = pool[j]
                for m in range(len(val_a[pos])):
                    vm = float(val_a[pos, m])
                    if not np.isfinite(vm):
                        continue
                    t = tuple(int(x) for x in idx_a[pos, m])
                    if vm < pj.get(t, np.inf):
                        pj[t] = vm
            return out

        pulls = np.zeros((n_jobs, n_back), dtype=np.int64)

        def _record_pull(j: int, b_idx: int) -> None:
            """Bookkeeping shared by every phase: the per-(job, backend)
            pull counter plus the process-wide pull family."""
            pulls[j, b_idx] += 1
            _M_PULLS.inc(backend=names[b_idx],
                         allocator=settings.allocator)

        def _fin(v: float) -> float | None:
            return float(v) if np.isfinite(v) else None

        def _publish(phase: str, rung: int,
                     jobs_touched: typing.Iterable[int],
                     rewards: dict | None = None,
                     ucb=None, chosen: dict | None = None) -> None:
            """One progress event per touched job after a race wave (the
            SSE ``progress`` payload; no-op when the caller didn't pass
            ``job_keys``).  The identical payload lands on the flight
            recorder, extended with the wave's bandit internals
            (``rewards`` per job, UCB ``scores`` and the ``chosen``
            arm) so timelines reconcile with the SSE stream exactly.
            ``chosen`` maps job -> backend index for the jobs that made
            an ADAPTIVE pull this wave; initialization pulls carry no
            UCB state, so a mixed wave (admitted jobs initializing next
            to veterans) only attaches ucb/chosen to the veterans."""
            if job_keys is None:
                return
            for j in jobs_touched:
                payload = dict(
                    phase=phase, allocator=settings.allocator,
                    rung=rung, best=_fin(best_val[j]),
                    backend_best={name: _fin(per_backend[j, b])
                                  for b, name in enumerate(names)},
                    pulls={name: int(pulls[j, b])
                           for b, name in enumerate(names)},
                    devices=n_devices)
                bus.publish(job_keys[j], **payload)
                if rewards is not None and j in rewards:
                    payload["rewards"] = rewards[j]
                if chosen is not None and j in chosen:
                    if ucb is not None:
                        payload["ucb"] = {name: _fin(ucb[j, b])
                                          for b, name in enumerate(names)}
                    payload["chosen"] = names[int(chosen[j])]
                recorder.event(job_keys[j], payload)

        # cross-job budget-flow accounting (bandit allocator only; the
        # halving branch leaves the defaults, so ``search["budget_flow"]``
        # reads uniformly for every portfolio result)
        flatlined = [False] * n_jobs
        released = [0] * n_jobs
        absorbed = [0] * n_jobs
        admit_wave = [0] * n_jobs
        spare_pulls = 0

        if settings.allocator == "halving":
            alive = np.ones((n_jobs, n_back), dtype=bool)
            for rung_no, rung in enumerate(race_plan(settings)):
                _M_RUNGS.inc(allocator="halving")
                with obs.span("engine.portfolio.rung", allocator="halving",
                              rung=rung_no, jobs=n_jobs):
                    handles = [
                        _launch(b_idx, rung[name],
                                [j for j in range(n_jobs)
                                 if alive[j, b_idx]])
                        for b_idx, name in enumerate(names)]
                    for h in handles:
                        if h is not None:
                            for j in _collect(h):
                                _record_pull(j, h[0])
                _publish("race", rung_no, range(n_jobs))
                # cull: each job keeps its best ceil(k/2) survivors
                for j in range(n_jobs):
                    live = np.flatnonzero(alive[j])
                    keep = -(-len(live) // 2)
                    order = live[np.argsort(per_backend[j, live],
                                            kind="stable")]
                    alive[j, order[keep:]] = False
        else:                                          # "bandit"
            # continuous-batching wave scheduler: every job carries its
            # OWN pull schedule (counters, rewards, derived seeds), so a
            # closed batch replays the classic init-then-adaptive race
            # bit-for-bit while late-admitted jobs start at pull 0 and
            # follow exactly their solo trajectory (the seed of pull p
            # is derived_seed(seed, backend, p) -- batch-independent)
            sum_reward = np.zeros((n_jobs, n_back))
            base_rounds = bandit_rounds(settings)
            flow_on = settings.flatline_waves > 0
            needs_init = [True] * n_jobs
            race_budget = [base_rounds] * n_jobs
            flat_run = [0] * n_jobs   # consecutive flat adaptive pulls
            wave = 0

            def _admit_pending() -> None:
                """Pull the caller's admission hook and extend every
                per-job state row for the newcomers (they join the next
                wave's initialization pulls)."""
                nonlocal n_jobs, best_val, best_idx, per_backend, \
                    pulls, sum_reward
                for key, p in admit():
                    batch.append(p)
                    job_keys.append(key)
                    best_val = np.append(best_val, np.inf)
                    best_idx = np.concatenate(
                        [best_idx, np.zeros((1, 5), dtype=np.int64)])
                    per_backend = np.concatenate(
                        [per_backend, np.full((1, n_back), np.inf)])
                    pulls = np.concatenate(
                        [pulls, np.zeros((1, n_back), dtype=np.int64)])
                    sum_reward = np.concatenate(
                        [sum_reward, np.zeros((1, n_back))])
                    member_vals.append(None)
                    traces.append(None)
                    pool.append(dict())
                    needs_init.append(True)
                    race_budget.append(base_rounds)
                    flat_run.append(0)
                    flatlined.append(False)
                    released.append(0)
                    absorbed.append(0)
                    admit_wave.append(wave)
                    n_jobs += 1
                    recorder.start(
                        key, method="portfolio",
                        allocator=settings.allocator,
                        backends=list(names), devices=n_devices,
                        device_map=device_map,
                        total_evals=settings.total_evals,
                        rungs=settings.rungs, seed=settings.seed,
                        admitted_wave=wave)

            while True:
                if admit is not None:
                    _admit_pending()
                # plan the wave: newcomers initialize (one pull per
                # backend, == halving's rung 0); veterans with budget
                # make their UCB-argmax adaptive pull (stable: ties
                # resolve to the lower backend index); spent-but-hot
                # jobs drain the shared pool one pull per wave
                init_jobs = [j for j in range(n_jobs) if needs_init[j]]
                chosen: dict[int, int] = {}
                scores = None
                spent = pulls.sum(axis=1)
                ready = [j for j in range(n_jobs)
                         if not needs_init[j] and not flatlined[j]]
                if ready:
                    scores = ucb_scores(
                        sum_reward / np.maximum(pulls, 1), pulls,
                        settings.ucb_c)
                    choice = np.argmax(scores, axis=1)
                    for j in ready:
                        if spent[j] < race_budget[j]:
                            chosen[j] = int(choice[j])
                        elif spare_pulls > 0:
                            spare_pulls -= 1
                            absorbed[j] += 1
                            chosen[j] = int(choice[j])
                            _M_SCHED_ABSORBED.inc()
                            if job_keys is not None:
                                fp = dict(
                                    phase="budget_flow", action="absorb",
                                    allocator=settings.allocator,
                                    rung=wave, absorbed=absorbed[j],
                                    pool=spare_pulls)
                                bus.publish(job_keys[j], **fp)
                                recorder.event(job_keys[j], fp)
                if not init_jobs and not chosen:
                    break
                _M_RUNGS.inc(allocator="bandit")
                prev = best_val.copy()
                touched: set[int] = set()
                wave_rewards: dict[int, dict[str, float]] = {}
                with obs.span("engine.portfolio.rung",
                              allocator="bandit", rung=wave,
                              jobs=n_jobs):
                    handles = []
                    for b_idx in range(n_back):
                        sel = sorted(set(init_jobs) |
                                     {j for j, b in chosen.items()
                                      if b == b_idx})
                        if not sel:
                            continue
                        handles.append(_launch(
                            b_idx, bandit_pull_plan(settings, b_idx, 0),
                            sel,
                            seed_rows=[derived_seed(settings.seed, b_idx,
                                                    int(pulls[j, b_idx]))
                                       for j in sel]))
                    for h in handles:
                        for j, (_v, r) in _collect(h, prev).items():
                            sum_reward[j, h[0]] += r
                            _record_pull(j, h[0])
                            touched.add(j)
                            wave_rewards.setdefault(j, {})[
                                names[h[0]]] = float(r)
                            if flow_on and j in chosen:
                                flat_run[j] = 0 \
                                    if r >= settings.flatline_eps \
                                    else flat_run[j] + 1
                for j in init_jobs:
                    needs_init[j] = False
                _publish("race", wave, sorted(touched),
                         rewards=wave_rewards, ucb=scores, chosen=chosen)
                if flow_on:
                    # flatline release: a job whose improvement rate
                    # dried up hands its unspent race pulls to the pool
                    spent = pulls.sum(axis=1)
                    for j in range(n_jobs):
                        if flatlined[j] or needs_init[j] or \
                                flat_run[j] < settings.flatline_waves:
                            continue
                        rem = int(race_budget[j] - spent[j])
                        flatlined[j] = True
                        _M_SCHED_FLATLINED.inc()
                        if rem > 0:
                            released[j] = rem
                            race_budget[j] = int(spent[j])
                            spare_pulls += rem
                            _M_SCHED_RELEASED.inc(rem)
                        if job_keys is not None:
                            fp = dict(
                                phase="budget_flow", action="release",
                                allocator=settings.allocator, rung=wave,
                                released=rem, pool=spare_pulls,
                                spent=int(spent[j]))
                            bus.publish(job_keys[j], **fp)
                            recorder.event(job_keys[j], fp)
                wave += 1

        # exploitation: the per-job winner gets the remaining budget
        # (kept out of per_backend so `race` stays race-phase-only)
        winners = per_backend.argmin(axis=1)
        final = final_plan(settings)
        final_best = np.full(n_jobs, np.inf)
        with obs.span("engine.portfolio.final", allocator=settings.allocator,
                      jobs=n_jobs):
            handles = [
                _launch(b_idx, final[name],
                        [j for j in range(n_jobs) if winners[j] == b_idx])
                for b_idx, name in enumerate(names)]
            for h in handles:
                if h is None:
                    continue
                for j, (v, _r) in _collect(h, fold_race=False).items():
                    final_best[j] = v

        # measured fidelity: re-score each job's top-K analytic
        # candidates under kernel-measurement-calibrated tech constants
        # and report both rankings plus their rank correlation
        two_fidelity: list[dict | None] = [None] * n_jobs
        measured_prep: list[_PreparedJob | None] = [None] * n_jobs
        measured_idx: list[np.ndarray | None] = [None] * n_jobs
        measured_val = np.full(n_jobs, np.inf)
        if getattr(settings, "fidelity", "analytic") == "measured":
            from repro.core.calibration import (
                calibration_version,
                resolve_corrections,
            )

            with obs.span("engine.portfolio.measured",
                          allocator=settings.allocator, jobs=n_jobs):
                cf, source, meas_records = resolve_corrections()
                version = calibration_version(cf)
                topk = int(getattr(settings, "topk", 8))
                p_cal = [
                    p._replace(job=dataclasses.replace(
                        p.job, tech=p.job.tech.with_corrections(cf)))
                    for p in batch]
                stacked_a = _stack_jobs([_job_arrays(p) for p in batch])
                stacked_m = _stack_jobs([_job_arrays(p) for p in p_cal])
                top_rows, cand_rows = [], []
                for j, p in enumerate(batch):
                    # deterministic top-K: analytic value, then axis
                    # indices break ties
                    ranked = sorted(pool[j].items(),
                                    key=lambda kv: (kv[1], kv[0]))[:topk]
                    top_rows.append([t for t, _v in ranked])
                    cand_rows.append(np.stack([
                        np.concatenate(
                            [p.mat[np.arange(5), np.asarray(t)],
                             [float(p.job.bw)]])
                        for t, _v in ranked]))
                vals_a = self._sweep_values(
                    batch[0].ops_pad, stacked_a, cand_rows)
                vals_m = self._sweep_values(
                    batch[0].ops_pad, stacked_m, cand_rows)
                for j, p in enumerate(batch):
                    va, vm = vals_a[j], vals_m[j]
                    order_a = np.argsort(va, kind="stable")
                    order_m = np.argsort(vm, kind="stable")
                    w = int(order_m[0])
                    measured_prep[j] = p_cal[j]
                    measured_idx[j] = np.asarray(top_rows[j][w],
                                                 dtype=np.int64)
                    measured_val[j] = float(vm[w])
                    two_fidelity[j] = {
                        "source": source,
                        "calibration_version": version,
                        "corrections": cf.as_dict(),
                        "topk": len(va),
                        "measurement_count": len(meas_records),
                        "analytic_ranking": [int(x) for x in order_a],
                        "measured_ranking": [int(x) for x in order_m],
                        "analytic_values": [float(x) for x in va],
                        "measured_values": [float(x) for x in vm],
                        "rank_correlation": _spearman(va, vm),
                        "analytic_winner": [
                            int(x)
                            for x in cand_rows[j][int(order_a[0])][:5]],
                        "measured_winner": [
                            int(x) for x in cand_rows[j][w][:5]],
                    }
                    if job_keys is not None:
                        # parked for the queue to persist as the result's
                        # .measurements.json store sidecar
                        obs.profile.record_measurements(
                            job_keys[j], meas_records)

        if job_keys is not None:
            for j in range(n_jobs):
                payload = dict(
                    phase="final", allocator=settings.allocator,
                    winner=names[int(winners[j])], best=_fin(best_val[j]),
                    final=_fin(final_best[j]),
                    pulls={name: int(pulls[j, b])
                           for b, name in enumerate(names)},
                    devices=n_devices)
                bus.publish(job_keys[j], **payload)
                recorder.event(job_keys[j], payload)
                if two_fidelity[j] is not None:
                    mp = dict(
                        phase="measured", allocator=settings.allocator,
                        best=_fin(measured_val[j]),
                        rank_correlation=two_fidelity[j][
                            "rank_correlation"],
                        topk=two_fidelity[j]["topk"],
                        calibration=two_fidelity[j][
                            "calibration_version"],
                        devices=n_devices)
                    bus.publish(job_keys[j], **mp)
                    recorder.event(job_keys[j], mp)
                recorder.finish(
                    job_keys[j], winner=payload["winner"],
                    best=payload["best"], final=payload["final"],
                    pulls=payload["pulls"])

        results = []
        for j, p in enumerate(batch):
            if measured_prep[j] is not None:
                # the measured winner, finished under calibrated
                # constants, IS the answer of a two-fidelity race
                out = self._wrap_search_winner(
                    measured_prep[j], "portfolio",
                    measured_idx[j][None, :],
                    np.asarray([measured_val[j]]), traces[j])
            else:
                out = self._wrap_search_winner(
                    p, "portfolio", best_idx[j][None, :],
                    np.asarray([best_val[j]]), traces[j])
            out.search["portfolio"] = {
                "winner": names[int(winners[j])],
                "allocator": settings.allocator,
                "race": {name: float(per_backend[j, b])
                         for b, name in enumerate(names)},
                "pulls": {name: int(pulls[j, b])
                          for b, name in enumerate(names)},
                "final": float(final_best[j]),
                "rungs": settings.rungs,
                "total_evals": settings.total_evals,
                "devices": sum(d is not None for d in devices) or 1,
                "fidelity": getattr(settings, "fidelity", "analytic"),
            }
            out.search["budget_flow"] = {
                "enabled": settings.allocator == "bandit"
                and settings.flatline_waves > 0,
                "flatlined": bool(flatlined[j]),
                "released": int(released[j]),
                "absorbed": int(absorbed[j]),
                "race_pulls": int(pulls[j].sum()),
                "pool_leftover": int(spare_pulls),
                "admitted_wave": int(admit_wave[j]),
            }
            if two_fidelity[j] is not None:
                out.search["two_fidelity"] = two_fidelity[j]
            out.sa = out.sa._replace(
                best_per_chain=jnp.asarray(member_vals[j]))
            results.append(out)
        return results

    # ---- exhaustive path ------------------------------------------ #
    def _pruned_candidates(self, p: _PreparedJob, fallback: bool = False,
                           ) -> tuple[np.ndarray, dict]:
        job = p.job
        with _phase("prune", job=p.key, **_fallback_arg(fallback)):
            rows, stats = prune_rows(
                job.design_space(), job.macro, job.area_budget_mm2,
                job.bw, job.tech)
            if len(rows) == 0:
                raise ValueError("no feasible hardware point under budget")
            return rows, stats

    def _sweep_values(
        self, ops_pad: int, stacked: cost_model.JobParams,
        cand_rows: list[np.ndarray], fallback: bool = False,
    ) -> list[np.ndarray]:
        """Evaluate per-job candidate lists in shared [J, CHUNK] blocks;
        each block's call, until its values are on the host, is one
        ``engine.executable`` phase."""
        chunk = self.EXHAUSTIVE_CHUNK
        fn = self._exhaustive_executable(ops_pad)
        n_max = max(len(c) for c in cand_rows)
        vals = [np.empty(len(c)) for c in cand_rows]
        for lo in range(0, n_max, chunk):
            # jobs exhaust their lists at different points; pad every lane
            # to the full chunk with its own first row (values discarded)
            lanes = []
            for c in cand_rows:
                part = c[lo: lo + chunk]
                if len(part) < chunk:
                    fill = np.repeat(c[:1], chunk - len(part), axis=0)
                    part = np.concatenate([part, fill], axis=0) \
                        if len(part) else np.repeat(c[:1], chunk, axis=0)
                lanes.append(part)
            block = np.stack(lanes, axis=0)                  # [J, chunk, 6]
            with _phase("executable", block=lo // chunk, jobs=len(lanes),
                        **_fallback_arg(fallback)):
                out = np.asarray(fn(stacked, jnp.asarray(block)))
            for jx, c in enumerate(cand_rows):
                take = min(max(len(c) - lo, 0), chunk)
                if take:
                    vals[jx][lo: lo + take] = out[jx, :take]
        return vals

    def _run_exhaustive_batch(
        self, batch: list[_PreparedJob],
    ) -> list[ExploreResult]:
        stacked = _stack_jobs([_job_arrays(p) for p in batch])
        cands, prune_stats = zip(*[self._pruned_candidates(p) for p in batch])
        vals = self._sweep_values(batch[0].ops_pad, stacked, list(cands))
        results = []
        for p, c, v, st in zip(batch, cands, vals, prune_stats):
            with _phase("finish", job=p.key):
                best = int(np.argmin(v))
                cfg = AcceleratorConfig(
                    *[int(x) for x in c[best][:5]], bw=p.job.bw)
                search = {"method": "exhaustive",
                          "merged_ops": len(p.workload.ops),
                          "raw_ops": len(p.job.workload.ops), **st}
                results.append(self._finish(p, cfg, search, None))
        return results

    def _exhaustive_one(self, p: _PreparedJob) -> tuple[AcceleratorConfig,
                                                        dict]:
        """Pruned-space optimum of a single job (SA snap-fallback)."""
        rows, stats = self._pruned_candidates(p, fallback=True)
        stacked = _stack_jobs([_job_arrays(p)])
        v = self._sweep_values(p.ops_pad, stacked, [rows], fallback=True)[0]
        best = int(np.argmin(v))
        return AcceleratorConfig(
            *[int(x) for x in rows[best][:5]], bw=p.job.bw), stats

    # ---- shared epilogue ------------------------------------------ #
    def _finish(self, p: _PreparedJob, cfg: AcceleratorConfig, search: dict,
                sa_res: SearchResult | None) -> ExploreResult:
        job = p.job
        fn = self._finish_executable(p.ops_pad)
        lat, en, area, true_ops, *idx = np.asarray(fn(_finish_row(p, cfg)))[0]
        metrics = cost_model.metrics_dict(
            lat, en, idx, area, true_ops, freq_mhz=job.macro.freq_mhz,
            n_ops=len(p.workload.ops))
        per_op = {
            op.name or f"op{i}":
                str(ALL_STRATEGIES[metrics["strategy_idx"][i]])
            for i, op in enumerate(p.workload.ops)
        }
        return ExploreResult(
            config=cfg,
            macro=job.macro,
            workload=job.workload.name,
            objective=job.objective,
            strategy_set=job.strategy_set,
            per_op_strategy=per_op,
            metrics={k: v for k, v in metrics.items()
                     if k != "strategy_idx"},
            search=search,
            sa=sa_res,
        )


# --------------------------------------------------------------------- #
# process-wide default engine (shared executable cache)
# --------------------------------------------------------------------- #
_default_engine: ExplorationEngine | None = None


def default_engine() -> ExplorationEngine:
    """The process-wide engine (one shared executable cache); created
    lazily on first use and shared by the ``co_explore`` family and the
    service queue so interleaved callers amortize compiles."""
    global _default_engine
    if _default_engine is None:
        _default_engine = ExplorationEngine()
    return _default_engine
