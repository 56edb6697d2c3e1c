"""Plain reference of the co-exploration answer, in numpy.

It restates the paper's closed-form cost model (CIM-Tuner, arXiv:2601.18070,
Sec. III-B/III-C: three-stage pipeline template, eight mapping strategies)
from the configuration file alone: the networks' operator lists, the macro,
the technology constants, the design space and the strategy sets.  It
imports nothing of the program and is written for clarity, not speed: every
(candidate, operator, strategy) is evaluated as one element of a numpy
array, in the precision it is given (float64 for the check, bfloat16 for the
lower-precision control).

What it answers, for one job (network, strategy set, objective, budget):

- the exhaustive optimum over the pruned design space (bandwidth and area
  rules, candidates in the space's product order, first index on ties);
- the metrics (cycles, pJ, mm^2, TOPS/W, GOPS) and per-operator best
  strategies of any configuration of the space.
"""
from __future__ import annotations

import dataclasses
import itertools

import ml_dtypes
import numpy as np

INFEASIBLE = 1e30
AXES = ("mr", "mc", "scr", "is_kb", "os_kb")
SPATIAL = ("NR", "R")
TEMPORAL = ("IP", "WP")
TILING = ("AF", "PF")
#: the eight strategies in index order: spatial x temporal x tiling
STRATEGIES = tuple(f"{s}-{t}-{f}" for s, t, f in
                   itertools.product(SPATIAL, TEMPORAL, TILING))

DTYPES = {"float64": np.float64, "bfloat16": ml_dtypes.bfloat16}


def merge_ops(ops: list) -> list:
    """Operators of equal (m, k, n, static) gathered, counts summed, in
    order of first appearance; unnamed operators get ``op<i>``."""
    merged: dict = {}
    for m, k, n, count, static, name in ops:
        key = (m, k, n, bool(static))
        if key in merged:
            merged[key][3] += count
        else:
            merged[key] = [m, k, n, count, bool(static), name]
    return [[*row[:5], row[5] or f"op{i}"]
            for i, row in enumerate(merged.values())]


@dataclasses.dataclass
class JobAnswer:
    """The reference's view of one configuration for one job."""

    cfg: tuple                 # (mr, mc, scr, is_kb, os_kb)
    feasible: bool             # inside the pruned space of the budget
    value: float               # job objective (inf when not feasible)
    metrics: dict              # latency_cycles, energy_pj, area_mm2, ...
    op_scores: dict            # op name -> {strategy: score}


class Reference:
    """Cost tables of one configuration file's networks over the whole
    design space, computed once per network and shared by every job."""

    def __init__(self, config: dict, dtype: str = "float64"):
        self.config = config
        self.dt = DTYPES[dtype]
        self.macro = config["macro"]
        self.tech = config["tech"]
        self.bw = float(config["bw"])
        space = config["design_space"]
        self.raw = np.array(list(itertools.product(
            *[space[a] for a in AXES])), dtype=np.int64)        # [C, 5]
        self.index = {tuple(int(v) for v in row): i
                      for i, row in enumerate(self.raw)}
        self.sets = {name: np.array([s in allowed for s in STRATEGIES])
                     for name, allowed in config["strategy_sets"].items()}
        self.ops = {name: merge_ops(ops)
                    for name, ops in config["networks"].items()}
        self._area64 = self._area(np.float64)
        self.bw_ok = ((self.macro["icw"] * self.raw[:, 0] >= self.bw)
                      & (self.macro["wuw"] * self.raw[:, 0]
                         * self.raw[:, 1] >= self.bw))
        self._tables: dict = {}
        self._totals_cache: dict = {}

    # ---- area and pruning (float64 like the program's host pruning) -- #
    def _area(self, dt) -> np.ndarray:
        mac, tech = self.macro, self.tech
        mr, mc, scr, is_kb, os_kb = (self.raw[:, i].astype(dt)
                                     for i in range(5))
        cells = mac["al"] * mac["pc"] * scr * mac["dw_w"] \
            * dt(tech["a_cell_um2_bit"])
        cus = dt(mac["al"] * mac["pc"] * tech["a_cu_um2"])
        macro_area = (cells + cus) * dt(1e-6) + dt(tech["a_macro_fixed_mm2"])

        def sram(kb):
            return kb * dt(8.0 / 1024.0) * dt(tech["a_sram_mm2_per_mb"]) \
                + dt(tech["a_sram_fixed_mm2"])
        return (mr * mc * macro_area + sram(is_kb) + sram(os_kb)
                + dt(tech["a_fixed_mm2"]))

    def pruned(self, budget: float) -> np.ndarray:
        """Mask of the candidates a job of this budget may choose."""
        return self.bw_ok & (self._area64 <= budget)

    # ---- the closed form: [C, 8] latency and energy of one operator --- #
    def _op_table(self, m, k, n) -> tuple[np.ndarray, np.ndarray]:
        dt = self.dt
        mac, tech = self.macro, self.tech
        c = lambda x: np.asarray(x, dtype=dt)            # noqa: E731
        mr, mc, scr, is_kb, os_kb = (c(self.raw[:, i])[:, None]
                                     for i in range(5))
        bits = np.array([[s == "R" for s in SPATIAL for _ in range(4)],
                         [t == "WP" for _ in SPATIAL for t in TEMPORAL
                          for _ in TILING],
                         [f == "PF" for _ in range(4) for f in TILING]])
        rev, wp, pf = (b[None, :] for b in bits)          # [1, 8] bools
        al, pc = c(mac["al"]), c(mac["pc"])
        dw_in, dw_w = c(mac["dw_in"]), c(mac["dw_w"])
        dw_psum, dw_out = c(mac["dw_psum"]), c(mac["dw_out"])
        one, zero = c(1.0), c(0.0)

        def ceil(a, b):
            return np.ceil(a / b).astype(dt)

        def floor(a, b):
            return np.floor(a / b).astype(dt)

        def spill(work, rows):
            return np.maximum(zero, work - rows)

        M = np.where(rev, c(n), c(m))
        N = np.where(rev, c(m), c(n))
        K = c(k)
        dws = np.where(rev, dw_w, dw_in)         # streamed operand width
        dwt = np.where(rev, dw_in, dw_w)         # stationary operand width
        cyc_c = np.maximum(one, ceil(dws * al, c(mac["icw"])))
        cyc_u = np.maximum(one, ceil(al * dwt, c(mac["wuw"])))

        Kp, Np = mr * al, mc * pc
        tK, tN = ceil(K, Kp), ceil(N, Np)
        Kpad, Npad = tK * Kp, tN * Np
        planes = tK * tN
        G, H = ceil(tK, scr), ceil(tN, scr)
        remN = tN - (H - one) * scr
        scr_n = np.minimum(scr, tN)
        is_bits = is_kb * c(8192.0)
        os_bits = os_kb * c(8192.0)

        rows_raw = floor(is_bits, Kpad * dws)
        wp_ok = rows_raw >= one
        rows = np.clip(rows_raw, one, M)
        B = ceil(M, rows)
        remB = M - (B - one) * rows
        is_ok = is_bits >= Kp * dws
        v_all = M * Kpad * dws <= is_bits

        v_refetch = np.where(v_all, one, np.where(pf, H, tN))
        v_bits = M * Kpad * dws * np.where(wp, one, v_refetch)
        s_loads = planes * np.where(wp & ~(planes <= scr), B, one)
        s_bits = s_loads * Kp * Np * dwt
        update = s_loads * cyc_u
        compute = M * planes * cyc_c
        macs = M * Kpad * Npad
        is_wr = v_bits
        is_rd = M * Kpad * dws * np.where(pf, H, tN)

        psum_row = Np * dw_psum
        os_rows_af = floor(os_bits, psum_row)
        spill_af_ip = c(2.0) * (G - one) * spill(M, os_rows_af) \
            * psum_row * tN
        spill_af_wp = c(2.0) * (G - one) * psum_row * tN * (
            (B - one) * spill(rows, os_rows_af) + spill(remB, os_rows_af))

        def pf_rows(work):
            full = floor(os_bits, scr_n * psum_row)
            last = floor(os_bits, remN * psum_row)
            return ((H - one) * spill(work, full) * scr_n
                    + spill(work, last) * remN)
        spill_pf_ip = c(2.0) * (tK - one) * psum_row * pf_rows(M)
        spill_pf_wp = c(2.0) * (tK - one) * psum_row * (
            (B - one) * pf_rows(rows) + pf_rows(remB))
        spill_bits = np.where(pf, np.where(wp, spill_pf_wp, spill_pf_ip),
                              np.where(wp, spill_af_wp, spill_af_ip))

        groups = np.where(pf, tK, G)
        os_wr = M * tN * groups * psum_row
        os_rd = M * tN * (groups - one) * psum_row + M * Npad * dw_psum
        os_ok = os_bits >= psum_row
        y_bits = M * Npad * dw_out

        ema_bits = v_bits + s_bits + spill_bits + y_bits
        ema_cycles = ceil(ema_bits, c(self.bw))
        overlap = bool(mac["update_during_compute"]) & (scr >= c(2.0))
        busy = np.maximum(compute, ema_cycles)
        latency = np.where(overlap, np.maximum(busy, update), busy + update)
        feasible = is_ok & os_ok & (~wp | wp_ok)

        mac_e = mac["e_mac_pj"] if mac.get("e_mac_pj") is not None \
            else tech["e_mac_pj"]
        e_dyn = (macs * c(mac_e)
                 + s_bits * c(tech["e_cim_update_pj_bit"])
                 + (is_rd + os_rd) * c(tech["e_sram_rd_pj_bit"])
                 + (is_wr + os_wr) * c(tech["e_sram_wr_pj_bit"])
                 + ema_bits * c(tech["e_ema_pj_bit"])) \
            * c(tech["sys_energy_overhead"])
        lat_s = latency / c(mac["freq_mhz"] * 1e6)
        area = self._area(dt)[:, None]
        energy = e_dyn + c(tech["p_leak_mw_mm2"]) * area * lat_s * c(1e9)
        big = c(INFEASIBLE)
        return (np.where(feasible, latency, big),
                np.where(feasible, energy, big))

    def tables(self, network: str):
        """``(lat, en)`` of shape [ops, C, 8] for one network."""
        if network not in self._tables:
            # low precisions can round a divisor to zero: the quotient is
            # then infinite, as it would be on the device
            with np.errstate(divide="ignore", invalid="ignore"):
                lat, en = zip(*[self._op_table(m, k, n)
                                for m, k, n, *_ in self.ops[network]])
            self._tables[network] = (np.stack(lat), np.stack(en))
        return self._tables[network]

    # ---- selection ---------------------------------------------------- #
    def _scores(self, network, strategy_set, objective):
        lat, en = self.tables(network)
        allowed = self.sets[strategy_set]
        big = np.asarray(INFEASIBLE, dtype=self.dt)
        lat = np.where(allowed, lat, big)
        en = np.where(allowed, en, big)
        if objective == "th":
            score = lat
        elif objective == "ee":
            score = en
        else:
            raise ValueError(f"unknown objective {objective!r}")
        return lat, en, score

    def _totals(self, network, strategy_set, objective):
        """Per-candidate workload totals with each operator's best
        strategy (first index on ties): ``(lat [C], en [C], pick)``."""
        key = (network, strategy_set, objective)
        if key not in self._totals_cache:
            self._totals_cache[key] = self._select(*key)
        return self._totals_cache[key]

    def _select(self, network, strategy_set, objective):
        lat, en, score = self._scores(network, strategy_set, objective)
        pick = np.argmin(score, axis=2)                      # [ops, C]
        take = lambda a: np.take_along_axis(                 # noqa: E731
            a, pick[..., None], axis=2)[..., 0]
        counts = np.array([op[3] for op in self.ops[network]],
                          dtype=self.dt)[:, None]
        return (np.sum(take(lat) * counts, axis=0, dtype=self.dt),
                np.sum(take(en) * counts, axis=0, dtype=self.dt), pick)

    def values(self, network, strategy_set, objective, budget):
        """Job objective of every raw candidate; inf outside the pruned
        space of ``budget``."""
        tl, te, _ = self._totals(network, strategy_set, objective)
        val = (tl if objective == "th" else te).astype(np.float64)
        return np.where(self.pruned(budget), val, np.inf)

    def optimum(self, network, strategy_set, objective, budget):
        """``(cfg, value)`` of the pruned-space optimum."""
        val = self.values(network, strategy_set, objective, budget)
        best = int(np.argmin(val))
        return tuple(int(v) for v in self.raw[best]), float(val[best])

    def answer(self, network, strategy_set, objective, budget,
               cfg) -> JobAnswer:
        """Everything the reference says about ``cfg`` for this job."""
        i = self.index.get(tuple(int(v) for v in cfg))
        if i is None:
            return JobAnswer(tuple(cfg), False, np.inf, {}, {})
        lat, en, score = self._scores(network, strategy_set, objective)
        tl, te, _ = self._totals(network, strategy_set, objective)
        ops = self.ops[network]
        true_ops = 2.0 * sum(float(m) * k * n * cnt
                             for m, k, n, cnt, *_ in ops)
        lat_s = float(tl[i]) / (self.macro["freq_mhz"] * 1e6)
        metrics = {
            "latency_cycles": float(tl[i]),
            "energy_pj": float(te[i]),
            "area_mm2": float(self._area(self.dt)[i]),
            "latency_s": lat_s,
            "tops_w": true_ops / (float(te[i]) * 1e-12) / 1e12,
            "gops": true_ops / lat_s / 1e9,
        }
        op_scores = {op[5]: {s: float(score[j, i, x])
                             for x, s in enumerate(STRATEGIES)
                             if self.sets[strategy_set][x]}
                     for j, op in enumerate(ops)}
        feasible = bool(self.pruned(budget)[i])
        value = float((tl if objective == "th" else te)[i])
        return JobAnswer(tuple(int(v) for v in cfg), feasible,
                         value if feasible else np.inf, metrics, op_scores)

    def area(self, cfg) -> float:
        i = self.index.get(tuple(int(v) for v in cfg))
        return float(self._area64[i]) if i is not None else np.inf
