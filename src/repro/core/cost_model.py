"""Closed-form latency/energy cost model for the generalized accelerator
template, covering all 8 mapping strategies (paper Sec. III-B/III-C).

The model is written as pure ``jnp`` arithmetic over scalars so that a single
``vmap`` stack evaluates *candidates x operators x strategies* in one shot --
this is what lets the hardware-mapping co-exploration be jitted, vmapped over
SA chains, batched over whole job lists (``core/engine.py``) and sharded over
a pod (``core/distributed.py``).

Macro and technology constants come in two flavours:

* static -- a :class:`~repro.core.macro.MacroSpec` / ``TechConstants`` pair
  (python scalars baked into the trace), the paper's fixed-macro workflow;
* traced -- :class:`MacroParams` / :class:`TechParams` NamedTuples whose
  leaves are arrays, so one jitted executable can evaluate *different*
  macros/technologies per job (the batched engine vmaps over a stacked job
  axis).  Both flavours run the identical formulas below.

Loop-nest semantics (NR orientation; R swaps M<->N and streamed/stationary
data widths).  ``V`` = streamed matrix (M x K, via Input SRAM), ``S`` =
stationary matrix (K x N, resident in CIM planes), output M x N via Output
SRAM.  The macro grid covers a physical tile of ``Kp x Np`` per plane
(Kp = MR*AL, Np = MC*PC); S is tiled into tK x tN planes; SCR planes are
co-resident.

    IP-AF:  for n_tile(tN): for k_group(G=ceil(tK/SCR)): for m: for plane
    IP-PF:  for n_group(H=ceil(tN/SCR)): for k_tile(tK): for m: for plane
    WP-AF:  for m_batch(B): for n_tile: for k_group: for m: for plane
    WP-PF:  for m_batch(B): for n_group: for k_tile: for m: for plane

Traffic/latency identities implemented below are matched *exactly* (integer
for integer) by the instruction-flow compiler's schedule sums
(``core/compiler.py``) -- property-tested in tests/test_cost_vs_compiler.py.
Latency uses a global three-stage-pipeline overlap bound; the cycle-accurate
simulator's per-set latency is sandwiched between the model's overlapped and
non-overlapped bounds (tests/test_simulator.py).

All arithmetic is float; run under ``repro.compat.enable_x64`` for exact
integer semantics (counts < 2^53), float32 otherwise (plenty for SA ordering).
"""
from __future__ import annotations

import typing

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.calibration import TechConstants, resolve_tech
from repro.core.macro import MacroSpec
from repro.core.strategies import ALL_STRATEGIES, STRATEGY_SETS

INFEASIBLE = 1e30

#: objective encodings shared by the string API and the traced batched API
OBJ_CODES: dict[str, int] = {"ee": 0, "th": 1, "edp": 2}


class MacroParams(typing.NamedTuple):
    """Traced-friendly view of a :class:`MacroSpec` (+ its energy override).

    Leaves are python floats in the static path and (possibly stacked)
    arrays in the batched path -- the cost formulas accept either.
    """

    al: typing.Any
    pc: typing.Any
    icw: typing.Any
    wuw: typing.Any
    dw_in: typing.Any
    dw_w: typing.Any
    dw_psum: typing.Any
    dw_out: typing.Any
    freq_mhz: typing.Any
    update_during_compute: typing.Any   # 0.0 / 1.0 ping-pong capability
    mac_e_pj: typing.Any                # per-MAC energy (macro override baked)


class TechParams(typing.NamedTuple):
    """Traced-friendly view of :class:`TechConstants` (energy/area/leakage)."""

    e_cim_update_pj_bit: typing.Any
    e_sram_rd_pj_bit: typing.Any
    e_sram_wr_pj_bit: typing.Any
    e_ema_pj_bit: typing.Any
    sys_energy_overhead: typing.Any
    p_leak_mw_mm2: typing.Any
    a_cell_um2_bit: typing.Any
    a_cu_um2: typing.Any
    a_macro_fixed_mm2: typing.Any
    a_sram_mm2_per_mb: typing.Any
    a_sram_fixed_mm2: typing.Any
    a_fixed_mm2: typing.Any


def macro_params(macro: MacroSpec,
                 tech: TechConstants | None = None) -> MacroParams:
    """Scalar (python-float) params of a macro -- the static baked path."""
    tech = resolve_tech(tech)
    return MacroParams(
        al=float(macro.al), pc=float(macro.pc),
        icw=float(macro.icw), wuw=float(macro.wuw),
        dw_in=float(macro.dw_in), dw_w=float(macro.dw_w),
        dw_psum=float(macro.dw_psum), dw_out=float(macro.dw_out),
        freq_mhz=float(macro.freq_mhz),
        update_during_compute=float(macro.update_during_compute),
        mac_e_pj=float(macro.mac_energy_pj(tech)),
    )


def tech_params(tech: TechConstants | None = None) -> TechParams:
    tech = resolve_tech(tech)
    return TechParams(
        e_cim_update_pj_bit=float(tech.e_cim_update_pj_bit),
        e_sram_rd_pj_bit=float(tech.e_sram_rd_pj_bit),
        e_sram_wr_pj_bit=float(tech.e_sram_wr_pj_bit),
        e_ema_pj_bit=float(tech.e_ema_pj_bit),
        sys_energy_overhead=float(tech.sys_energy_overhead),
        p_leak_mw_mm2=float(tech.p_leak_mw_mm2),
        a_cell_um2_bit=float(tech.a_cell_um2_bit),
        a_cu_um2=float(tech.a_cu_um2),
        a_macro_fixed_mm2=float(tech.a_macro_fixed_mm2),
        a_sram_mm2_per_mb=float(tech.a_sram_mm2_per_mb),
        a_sram_fixed_mm2=float(tech.a_sram_fixed_mm2),
        a_fixed_mm2=float(tech.a_fixed_mm2),
    )


def _as_params(macro, tech):
    """Normalize (MacroSpec|MacroParams, TechConstants|TechParams|None)."""
    mp = macro if isinstance(macro, MacroParams) else macro_params(
        macro, tech if isinstance(tech, TechConstants) else None)
    tp = tech if isinstance(tech, TechParams) else tech_params(
        tech if isinstance(tech, TechConstants) else None)
    return mp, tp


def objective_code(objective) -> typing.Any:
    """Map "ee"/"th"/"edp" to its integer code; pass traced codes through."""
    if isinstance(objective, str):
        try:
            return OBJ_CODES[objective]
        except KeyError:
            raise ValueError(
                f"unknown objective {objective!r}; "
                f"expected one of {sorted(OBJ_CODES)}") from None
    return objective


def _score(lat, en, code):
    """Per-objective scalar score (lower is better); ``code`` may be traced."""
    return jnp.where(code == OBJ_CODES["th"], lat,
                     jnp.where(code == OBJ_CODES["edp"], lat * en, en))


# The TPU's float32 divide is not correctly rounded: 21 / 7 comes out as
# 3.0000002, so ceil(a / b) lands one step high on an exact quotient (and
# floor one step low).  One multiply-and-compare step on each side makes
# both exact for integer operands below 2**24; where the divide is
# correctly rounded (the CPU) they change nothing.
def _snap_ceil(q, a, b):
    """``q`` = ceil of an approximate ``a / b`` -> the exact ceiling."""
    q = jnp.where((q - 1.0) * b >= a, q - 1.0, q)
    return jnp.where(q * b < a, q + 1.0, q)


def _snap_floor(q, a, b):
    """``q`` = floor of an approximate ``a / b`` -> the exact floor."""
    q = jnp.where(q * b > a, q - 1.0, q)
    return jnp.where((q + 1.0) * b <= a, q + 1.0, q)


def _ceil(a, b):
    return _snap_ceil(jnp.ceil(a / b), a, b)


def _fdiv(a, b):
    return _snap_floor(jnp.floor(a / b), a, b)


class CostBreakdown(typing.NamedTuple):
    """Per-operator-call cost terms (cycles, bits, pJ)."""

    latency_cycles: jax.Array
    compute_cycles: jax.Array
    update_cycles: jax.Array
    ema_cycles: jax.Array
    ema_bits: jax.Array          # total external traffic
    v_ema_bits: jax.Array        # streamed-matrix fetch
    s_ema_bits: jax.Array        # stationary-matrix (CIM update) fetch
    spill_ema_bits: jax.Array    # psum spills
    y_ema_bits: jax.Array        # output writeback
    is_rd_bits: jax.Array
    is_wr_bits: jax.Array
    os_rd_bits: jax.Array
    os_wr_bits: jax.Array
    update_bits: jax.Array       # CIM write traffic (== s_ema_bits)
    macs: jax.Array              # padded MACs actually executed
    energy_pj: jax.Array
    feasible: jax.Array


def matmul_cost(
    # operator (already oriented? no -- raw op dims)
    m, k, n,
    # strategy bits (0/1 floats): reversed, weight_priority, parallel_first
    rev, wp, pf,
    # accelerator config
    mr, mc, scr, is_kb, os_kb, bw, area_mm2,
    # macro (MacroSpec = static python constants, MacroParams = traceable)
    macro,
    tech=None,
) -> CostBreakdown:
    """Cost of one (m x k) @ (k x n) call under one strategy on one config.

    With a ``MacroSpec``/``TechConstants`` pair the macro constants are
    static (python) -- the paper fixes the macro during accelerator
    exploration.  With ``MacroParams``/``TechParams`` they may be traced and
    vmapped like everything else (the batched engine's per-job macros).
    """
    mp, tp = _as_params(macro, tech)
    one = jnp.float32(1.0).astype(jnp.result_type(float))
    m, k, n = (jnp.asarray(x) * one for x in (m, k, n))
    rev, wp, pf = (jnp.asarray(x) * one for x in (rev, wp, pf))
    mr, mc, scr = (jnp.asarray(x) * one for x in (mr, mc, scr))
    is_bits = jnp.asarray(is_kb) * one * 1024.0 * 8.0
    os_bits = jnp.asarray(os_kb) * one * 1024.0 * 8.0
    bw = jnp.asarray(bw) * one

    # ---- spatial scheduling: orientation + data widths -------------------
    M = jnp.where(rev > 0, n, m)
    N = jnp.where(rev > 0, m, n)
    K = k
    dws = jnp.where(rev > 0, mp.dw_w, mp.dw_in)   # streamed operand width
    dwt = jnp.where(rev > 0, mp.dw_in, mp.dw_w)   # stationary operand width
    dw_psum = mp.dw_psum
    dw_out = mp.dw_out

    # per-plane-op / per-plane-update cycles (eqns 3-5); depend on which
    # operand streams through the input drivers
    cyc_c = jnp.maximum(1.0, _ceil(dws * mp.al, mp.icw))
    cyc_u = jnp.maximum(1.0, _ceil(mp.al * dwt, mp.wuw))

    # ---- geometry ---------------------------------------------------------
    Kp = mr * mp.al
    Np = mc * mp.pc
    tK = _ceil(K, Kp)
    tN = _ceil(N, Np)
    Kpad = tK * Kp
    Npad = tN * Np
    planes = tK * tN

    G = _ceil(tK, scr)                      # AF groups per output column
    H = _ceil(tN, scr)                      # PF groups per K tile
    remN = tN - (H - 1.0) * scr             # planes in last PF group
    scr_n = jnp.minimum(scr, tN)

    # ---- Input SRAM residency --------------------------------------------
    # WP keeps full rows (width Kpad) resident across the whole weight sweep.
    rows_res_raw = _fdiv(is_bits, Kpad * dws)
    wp_feasible = rows_res_raw >= 1.0
    rows_res = jnp.clip(rows_res_raw, 1.0, M)
    B = _ceil(M, rows_res)                  # WP input batches
    remB = M - (B - 1.0) * rows_res         # rows in last batch
    # minimal functional IS requirement: one plane-chunk of the streamed row
    is_feasible = is_bits >= Kp * dws
    fits_all_v = M * Kpad * dws <= is_bits  # whole streamed matrix cached

    # ---- streamed-matrix (V) external traffic ----------------------------
    v_refetch_ip = jnp.where(fits_all_v, 1.0, jnp.where(pf > 0, H, tN))
    v_bits = M * Kpad * dws * jnp.where(wp > 0, 1.0, v_refetch_ip)

    # ---- stationary-matrix (S) external traffic + CIM updates ------------
    fits_all_s = planes <= scr
    s_loads = planes * jnp.where(
        (wp > 0) & ~fits_all_s, B, 1.0
    )                                        # plane loads from DRAM
    s_bits = s_loads * Kp * Np * dwt
    update_cycles = s_loads * cyc_u

    # ---- compute ----------------------------------------------------------
    compute_cycles = M * planes * cyc_c      # strategy-invariant
    macs = M * Kpad * Npad                   # padded MACs executed

    # ---- Input SRAM access ------------------------------------------------
    is_wr = v_bits                            # every fetched bit lands in IS
    # reads are compute-driven; PF reuses the row chunk across the group
    is_rd = M * Kpad * dws * jnp.where(pf > 0, H, tN)

    # ---- Output SRAM access + psum spills --------------------------------
    # AF: psum row width Np, accumulation transitions (G-1) per output column
    os_rows_af = _fdiv(os_bits, Np * dw_psum)
    # PF: psum working-set width q*Np for a group of q planes
    def _os_rows_pf(q):
        return _fdiv(os_bits, q * Np * dw_psum)

    def _spill(workrows, osrows):
        return jnp.maximum(0.0, workrows - osrows)

    # --- AF spills ---
    spill_af_ip = 2.0 * (G - 1.0) * _spill(M, os_rows_af) * Np * dw_psum * tN
    spill_af_wp = (
        2.0 * (G - 1.0) * Np * dw_psum * tN
        * ((B - 1.0) * _spill(rows_res, os_rows_af) + _spill(remB, os_rows_af))
    )
    spill_af = jnp.where(wp > 0, spill_af_wp, spill_af_ip)

    # --- PF spills (full groups of width scr_n, remainder group remN) ---
    nfull = H - 1.0
    def _pf_spill_rows(workrows):
        return (
            nfull * _spill(workrows, _os_rows_pf(scr_n)) * scr_n
            + _spill(workrows, _os_rows_pf(remN)) * remN
        )
    spill_pf_ip = 2.0 * (tK - 1.0) * Np * dw_psum * _pf_spill_rows(M)
    spill_pf_wp = 2.0 * (tK - 1.0) * Np * dw_psum * (
        (B - 1.0) * _pf_spill_rows(rows_res) + _pf_spill_rows(remB)
    )
    spill_pf = jnp.where(wp > 0, spill_pf_wp, spill_pf_ip)
    spill_bits = jnp.where(pf > 0, spill_pf, spill_af)

    # --- OS read/write (every psum passes through OS) ---
    groups_per_col = jnp.where(pf > 0, tK, G)   # psum writes per (row, col)
    os_wr = M * tN * groups_per_col * Np * dw_psum
    os_rd = M * tN * (groups_per_col - 1.0) * Np * dw_psum + M * Npad * dw_psum
    os_feasible = os_bits >= Np * dw_psum

    # ---- output writeback --------------------------------------------------
    y_bits = M * Npad * dw_out

    # ---- totals ------------------------------------------------------------
    ema_bits = v_bits + s_bits + spill_bits + y_bits
    ema_cycles = _ceil(ema_bits, bw)

    overlap = mp.update_during_compute * (scr >= 2.0)
    busy = jnp.maximum(compute_cycles, ema_cycles)
    latency = jnp.where(
        overlap,
        jnp.maximum(busy, update_cycles),
        busy + update_cycles,
    )

    feasible = is_feasible & os_feasible & ((wp == 0) | wp_feasible)

    # ---- energy ------------------------------------------------------------
    e_dyn = (
        macs * mp.mac_e_pj
        + s_bits * tp.e_cim_update_pj_bit
        + (is_rd + os_rd) * tp.e_sram_rd_pj_bit
        + (is_wr + os_wr) * tp.e_sram_wr_pj_bit
        + ema_bits * tp.e_ema_pj_bit
    ) * tp.sys_energy_overhead
    lat_s = latency / (mp.freq_mhz * 1e6)
    e_leak = tp.p_leak_mw_mm2 * area_mm2 * lat_s * 1e9  # mW*s -> pJ
    energy = e_dyn + e_leak

    latency = jnp.where(feasible, latency, INFEASIBLE)
    energy = jnp.where(feasible, energy, INFEASIBLE)

    return CostBreakdown(
        latency_cycles=latency,
        compute_cycles=compute_cycles,
        update_cycles=update_cycles,
        ema_cycles=ema_cycles,
        ema_bits=ema_bits,
        v_ema_bits=v_bits,
        s_ema_bits=s_bits,
        spill_ema_bits=spill_bits,
        y_ema_bits=y_bits,
        is_rd_bits=is_rd,
        is_wr_bits=is_wr,
        os_rd_bits=os_rd,
        os_wr_bits=os_wr,
        update_bits=s_bits,
        macs=macs,
        energy_pj=energy,
        feasible=feasible,
    )


# ---------------------------------------------------------------------- #
# vectorized stacks
# ---------------------------------------------------------------------- #
#: [8, 3] strategy bits (reversed, weight priority, parallel first).  A
#: numpy constant, so importing this module starts no JAX backend; traced
#: code converts it where it is used.
_STRAT_BITS = np.array(
    [[float(s.spatial == "R"), float(s.temporal == "WP"),
      float(s.tiling == "PF")] for s in ALL_STRATEGIES], np.float32)


def strategy_table(op_row, cfg_row, area_mm2, macro, tech=None):
    """Costs of one op under all 8 strategies.  op_row = (m,k,n,count,static),
    cfg_row = (mr,mc,scr,is_kb,os_kb,bw)."""
    def _one(bits):
        return matmul_cost(
            op_row[0], op_row[1], op_row[2],
            bits[0], bits[1], bits[2],
            cfg_row[0], cfg_row[1], cfg_row[2], cfg_row[3], cfg_row[4],
            cfg_row[5], area_mm2, macro, tech,
        )
    return jax.vmap(_one)(_STRAT_BITS)


def area_mm2_jnp(cfg_row, macro, tech=None):
    """jnp version of template.accelerator_area_mm2 (traced cfg and,
    via MacroParams/TechParams, optionally traced macro/tech)."""
    mp, tp = _as_params(macro, tech)
    mr, mc, scr, is_kb, os_kb = (cfg_row[i] for i in range(5))
    cells = mp.al * mp.pc * scr * mp.dw_w * tp.a_cell_um2_bit
    cus = mp.al * mp.pc * tp.a_cu_um2
    macro_area = (cells + cus) * 1e-6 + tp.a_macro_fixed_mm2
    sram = lambda kb: kb * 8.0 / 1024.0 * tp.a_sram_mm2_per_mb \
        + tp.a_sram_fixed_mm2
    return mr * mc * macro_area + sram(is_kb) + sram(os_kb) + tp.a_fixed_mm2


def bandwidth_ok_jnp(cfg_row, macro):
    mp, _ = _as_params(macro, None)
    bw = cfg_row[5]
    return (mp.icw * cfg_row[0] >= bw) & (
        mp.wuw * cfg_row[0] * cfg_row[1] >= bw
    )


def workload_cost_core(
    ops_arr, cfg_row, strat_bits, allowed, macro,
    tech=None, objective="ee",
):
    """workload_cost with the strategy tables passed in explicitly (lets the
    Pallas strategy_eval kernel feed them through refs instead of capturing
    module-level constants).  ``objective`` may be a string or a (possibly
    traced) integer code from :data:`OBJ_CODES`."""
    mp, tp = _as_params(macro, tech)
    code = objective_code(objective)
    area = area_mm2_jnp(cfg_row, mp, tp)

    def per_op(op_row):
        def _one(bits):
            return matmul_cost(
                op_row[0], op_row[1], op_row[2],
                bits[0], bits[1], bits[2],
                cfg_row[0], cfg_row[1], cfg_row[2], cfg_row[3], cfg_row[4],
                cfg_row[5], area, mp, tp,
            )
        tbl = jax.vmap(_one)(strat_bits)
        lat = jnp.where(allowed > 0, tbl.latency_cycles, INFEASIBLE)
        en = jnp.where(allowed > 0, tbl.energy_pj, INFEASIBLE)
        # argmin and its selection spelled as compare/where/reduce over the
        # 8 strategies: the TPU kernel compiler lowers no gather under a
        # vmap.  First index of the minimum (argmin's tie rule); summing
        # one picked value with zeros is exact, so results are unchanged.
        score = _score(lat, en, code)
        lanes = jnp.arange(score.shape[0])
        idx = jnp.min(jnp.where(score == jnp.min(score), lanes,
                                score.shape[0]))
        pick = lanes == idx
        return (jnp.sum(jnp.where(pick, lat, 0.0)),
                jnp.sum(jnp.where(pick, en, 0.0)), idx)

    lat, en, idx = jax.vmap(per_op)(ops_arr)
    counts = ops_arr[:, 3]
    total_lat = jnp.sum(lat * counts)
    total_en = jnp.sum(en * counts)
    return total_lat, total_en, idx


def strategy_mask(strategy_set: str) -> np.ndarray:
    """[8] 0/1 mask of the strategies ``strategy_set`` admits; a numpy
    constant, so building a job's arrays moves nothing to the device."""
    return np.array(
        [1.0 if s in STRATEGY_SETS[strategy_set] else 0.0
         for s in ALL_STRATEGIES]
    )


def workload_cost(
    ops_arr,                # [P, 5] (m, k, n, count, static); count==0 -> pad
    cfg_row,                # [6]
    macro,
    tech=None,
    objective="ee",         # "ee" (energy) | "th" (latency) | "edp"
    strategy_set: str = "st",
):
    """Best-strategy-per-operator workload cost on one accelerator config.

    Returns (total_latency_cycles, total_energy_pj, per_op_strategy_idx).
    The per-op argmin implements the fine-grained mapping exploration; the
    restriction mask reproduces the spatial-only baseline of [19].
    """
    return workload_cost_core(
        ops_arr, cfg_row, _STRAT_BITS, strategy_mask(strategy_set),
        macro, tech, objective)


def objective_value(total_lat, total_en, objective):
    """Scalar objective from workload totals; str or integer-code input."""
    return _score(total_lat, total_en, objective_code(objective))


# ---------------------------------------------------------------------- #
# per-job bundles for the batched exploration engine
# ---------------------------------------------------------------------- #
class JobParams(typing.NamedTuple):
    """Everything the objective needs about one job, as traceable leaves.

    Stacking a list of these along axis 0 (``jax.tree.map`` + ``stack``)
    yields the job axis the engine vmaps over; shapes must already agree
    (operator arrays padded to a shared bucket width by the engine).
    """

    ops: typing.Any          # [P, 5] (m, k, n, count, static)
    macro: MacroParams       # scalar leaves
    tech: TechParams         # scalar leaves
    allowed: typing.Any      # [8] strategy mask
    obj_code: typing.Any     # () int32
    area_budget: typing.Any  # () mm^2
    bw: typing.Any           # () external bus bits/cycle


def job_objective(job: JobParams, cfg_row, penalty_scale: float = 1e3):
    """Scalar objective(cfg_row[6]) of one job -- the traced twin of
    :func:`make_objective_fn` (area penalty always on; jobs carry budgets)."""
    lat, en, _ = workload_cost_core(
        job.ops, cfg_row, _STRAT_BITS, job.allowed, job.macro, job.tech,
        job.obj_code)
    val = _score(lat, en, job.obj_code)
    area = area_mm2_jnp(cfg_row, job.macro, job.tech)
    excess = jnp.maximum(0.0, area - job.area_budget) / job.area_budget
    val = val * (1.0 + penalty_scale * excess)
    return jnp.where(bandwidth_ok_jnp(cfg_row, job.macro), val, INFEASIBLE)


def make_objective_fn(
    ops_arr,
    macro,
    tech=None,
    objective="ee",
    strategy_set: str = "st",
    area_budget_mm2: float | None = None,
    penalty_scale: float = 1e3,
):
    """Scalar objective(cfg_row) for the SA / exhaustive explorers.

    Area-budget violation enters as a smooth multiplicative penalty so SA can
    walk the boundary; bandwidth-infeasible configs get the hard INFEASIBLE.
    """
    ops_arr = jnp.asarray(ops_arr)
    mp, tp = _as_params(macro, tech)
    code = objective_code(objective)
    mask = strategy_mask(strategy_set)

    def fn(cfg_row):
        lat, en, _ = workload_cost_core(
            ops_arr, cfg_row, _STRAT_BITS, mask, mp, tp, code
        )
        val = _score(lat, en, code)
        if area_budget_mm2 is not None:
            area = area_mm2_jnp(cfg_row, mp, tp)
            excess = jnp.maximum(0.0, area - area_budget_mm2) / area_budget_mm2
            val = val * (1.0 + penalty_scale * excess)
        val = jnp.where(bandwidth_ok_jnp(cfg_row, mp), val, INFEASIBLE)
        return val

    return fn


def workload_metrics_core(job: JobParams, cfg_row):
    """Traced workload totals of one job on one config: ``(total latency
    cycles, total energy pJ, per-op strategy index [P], area mm^2, true
    ops)``.  Padded operator rows (count 0) add nothing to any total; the
    engine jits this per operator bucket as its result epilogue."""
    lat, en, idx = workload_cost_core(
        job.ops, cfg_row, _STRAT_BITS, job.allowed, job.macro, job.tech,
        job.obj_code)
    ops = job.ops
    true_ops = 2.0 * jnp.sum(ops[:, 0] * ops[:, 1] * ops[:, 2] * ops[:, 3])
    return lat, en, idx, area_mm2_jnp(cfg_row, job.macro, job.tech), true_ops


def metrics_dict(total_lat, total_en, strategy_idx, area_mm2, true_ops,
                 freq_mhz, n_ops: int) -> dict:
    """The human-facing metrics of :func:`workload_metrics_core`'s outputs,
    as Python numbers; ``strategy_idx`` is cut to the ``n_ops`` real
    operators."""
    lat, en, ops = (np.float64(x) for x in (total_lat, total_en, true_ops))
    lat_s = lat / (float(freq_mhz) * 1e6)
    with np.errstate(divide="ignore", invalid="ignore"):
        tops_w = ops / (en * 1e-12) / 1e12
        gops = ops / lat_s / 1e9
    return {
        "latency_cycles": float(lat),
        "latency_s": float(lat_s),
        "energy_pj": float(en),
        "tops_w": float(tops_w),
        "gops": float(gops),
        "area_mm2": float(area_mm2),
        "strategy_idx": [int(i) for i in np.asarray(strategy_idx)[:n_ops]],
    }


def workload_metrics(
    workload_ops_arr,
    cfg_row,
    macro,
    tech=None,
    objective="ee",
    strategy_set: str = "st",
) -> dict:
    """Human-facing PPA metrics for a config (TOPS/W, GOPS, mm^2, ...),
    evaluated eagerly through :func:`workload_metrics_core`."""
    mp, tp = _as_params(macro, tech)
    ops_arr = jnp.asarray(workload_ops_arr)
    cfg_row = jnp.asarray(cfg_row)
    job = JobParams(
        ops=ops_arr, macro=mp, tech=tp, allowed=strategy_mask(strategy_set),
        obj_code=objective_code(objective), area_budget=float("inf"),
        bw=cfg_row[5])
    return metrics_dict(*workload_metrics_core(job, cfg_row),
                        freq_mhz=mp.freq_mhz, n_ops=len(ops_arr))
