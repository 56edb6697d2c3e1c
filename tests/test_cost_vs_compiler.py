"""THE core validation: the closed-form cost model must reproduce the
instruction-flow compiler's per-set schedule sums exactly (integer for
integer) for every strategy, and the address-level trace must perform the
exact matrix multiplication under IS/CIM/OS capacity invariants."""
import numpy as np
import pytest

from repro.compat import enable_x64

from repro.core import (
    ALL_STRATEGIES,
    AcceleratorConfig,
    compile_schedule,
    compile_trace,
    get_macro,
    matmul_cost,
    replay_trace,
    schedule_totals,
    strategy_feasible,
)

FIELDS = dict(
    v_bits="v_ema_bits", s_bits="s_ema_bits", spill_bits="spill_ema_bits",
    y_bits="y_ema_bits", is_rd_bits="is_rd_bits", is_wr_bits="is_wr_bits",
    os_rd_bits="os_rd_bits", os_wr_bits="os_wr_bits",
    compute_cycles="compute_cycles", update_cycles="update_cycles",
)


def _closed_form(macro, cfg, m, k, n, s):
    return matmul_cost(
        m, k, n,
        float(s.spatial == "R"), float(s.temporal == "WP"),
        float(s.tiling == "PF"),
        cfg.mr, cfg.mc, cfg.scr, cfg.is_kb, cfg.os_kb, cfg.bw,
        1.0, macro)


def _random_cases(n_cases, seed):
    rng = np.random.default_rng(seed)
    macros = [get_macro(x) for x in
              ("vanilla-dcim", "lcc-cim", "trancim-macro", "fpcim")]
    for i in range(n_cases):
        yield (
            macros[i % len(macros)],
            AcceleratorConfig(
                mr=int(rng.integers(1, 4)), mc=int(rng.integers(1, 4)),
                scr=int(2 ** rng.integers(0, 6)),
                is_kb=int(2 ** rng.integers(0, 8)),
                os_kb=int(2 ** rng.integers(0, 7)), bw=256),
            int(rng.integers(1, 80)), int(rng.integers(1, 600)),
            int(rng.integers(1, 500)),
        )


def test_closed_form_matches_compiler_exactly():
    checked = 0
    with enable_x64(True):
        for macro, cfg, m, k, n in _random_cases(40, seed=123):
            for s in ALL_STRATEGIES:
                if not strategy_feasible(macro, cfg, m, k, n, s):
                    continue
                tot = schedule_totals(compile_schedule(macro, cfg, m, k, n, s))
                cb = _closed_form(macro, cfg, m, k, n, s)
                for sf, cf in FIELDS.items():
                    assert tot[sf] == float(getattr(cb, cf)), (
                        f"{sf} mismatch: {s} op={(m, k, n)} "
                        f"cfg={cfg.as_tuple()} macro={macro.name}")
                checked += 1
    assert checked > 150


def test_compute_cycles_strategy_invariant():
    """Total plane-compute work is identical across temporal/tiling (padding
    aside) -- the mapping only re-orders it."""
    macro = get_macro("vanilla-dcim")
    cfg = AcceleratorConfig(2, 2, 8, 32, 16)
    with enable_x64(True):
        for (m, k, n) in ((64, 300, 200), (17, 100, 90)):
            vals = set()
            for s in ALL_STRATEGIES:
                if s.spatial == "R" or not strategy_feasible(
                        macro, cfg, m, k, n, s):
                    continue
                cb = _closed_form(macro, cfg, m, k, n, s)
                vals.add(float(cb.compute_cycles))
            assert len(vals) == 1


@pytest.mark.parametrize("strategy", ALL_STRATEGIES, ids=str)
def test_trace_replay_computes_matmul(strategy):
    """The compiled instruction flow performs the exact matrix product (the
    paper's silicon-verification 'validation script')."""
    rng = np.random.default_rng(7)
    macro = get_macro("vanilla-dcim")
    for cfg, (m, k, n) in [
        (AcceleratorConfig(2, 2, 4, 8, 2), (37, 200, 150)),
        (AcceleratorConfig(1, 1, 2, 4, 1), (9, 70, 40)),
        (AcceleratorConfig(3, 2, 16, 64, 8), (21, 500, 120)),
    ]:
        if not strategy_feasible(macro, cfg, m, k, n, strategy):
            continue
        x = rng.integers(-4, 4, (m, k)).astype(np.float64)
        w = rng.integers(-4, 4, (k, n)).astype(np.float64)
        tr = compile_trace(macro, cfg, m, k, n, strategy)
        y = replay_trace(tr, x, w, macro, cfg, strategy)
        np.testing.assert_allclose(y, x @ w)


def test_reversed_is_swap_symmetry():
    """R(m,k,n) == NR(n,k,m) when streamed/stationary widths are equal."""
    macro = get_macro("vanilla-dcim")
    cfg = AcceleratorConfig(2, 2, 4, 16, 8)
    with enable_x64(True):
        for s_idx in (0, 1, 2, 3):
            s = ALL_STRATEGIES[s_idx]            # NR variants
            r = ALL_STRATEGIES[s_idx + 4]        # matching R variants
            a = _closed_form(macro, cfg, 40, 300, 120, r)
            b = _closed_form(macro, cfg, 120, 300, 40, s)
            assert float(a.latency_cycles) == float(b.latency_cycles)
            assert float(a.ema_bits) == float(b.ema_bits)


def test_infeasible_strategies_get_sentinel():
    from repro.core.cost_model import INFEASIBLE
    macro = get_macro("fpcim")    # AL=128 -> big rows
    # IS too small to hold one full row: WP infeasible, IP fine
    cfg = AcceleratorConfig(2, 1, 2, 1, 8)      # 1 KB IS
    m, k, n = 32, 4096, 256
    with enable_x64(True):
        wp = _closed_form(macro, cfg, m, k, n, ALL_STRATEGIES[2])  # NR-WP-AF
        ip = _closed_form(macro, cfg, m, k, n, ALL_STRATEGIES[0])  # NR-IP-AF
    assert float(wp.latency_cycles) == INFEASIBLE
    assert float(ip.latency_cycles) < INFEASIBLE


@pytest.mark.parametrize("way", ["ceil", "floor"])
def test_integer_division_exact_under_an_inexact_divide(way):
    """The cost model's ceil/floor of integer quotients stay exact in
    float32 when the divide lands one ulp off either way, as the TPU's
    does (21 / 7 -> 3.0000002), and equal plain ceil/floor where it is
    correctly rounded."""
    import jax.numpy as jnp

    from repro.core import cost_model

    a, b = np.meshgrid(np.arange(1, 4097, dtype=np.float32),
                       np.arange(1, 257, dtype=np.float32))
    ia, ib = a.astype(np.int64), b.astype(np.int64)
    exact = -(-ia // ib) if way == "ceil" else ia // ib
    snap = cost_model._snap_ceil if way == "ceil" else cost_model._snap_floor
    rnd = np.ceil if way == "ceil" else np.floor
    q = a / b
    for off in (np.nextafter(q, np.float32(np.inf)), q,
                np.nextafter(q, np.float32(-np.inf))):
        got = np.asarray(snap(jnp.asarray(rnd(off)), a, b))
        np.testing.assert_array_equal(got.astype(np.int64), exact)
    plain = cost_model._ceil if way == "ceil" else cost_model._fdiv
    np.testing.assert_array_equal(
        np.asarray(plain(jnp.asarray(a), jnp.asarray(b))), rnd(q))
