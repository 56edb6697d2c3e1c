"""Two-fidelity portfolio race: the measured final rung, job-key
separation between fidelities, deterministic replay under a pinned
calibration artifact, and the unified submit contract's fidelity
normalization."""
from __future__ import annotations

import dataclasses

import pytest

from repro.core import (
    DesignSpace,
    ExplorationEngine,
    ExploreJob,
    bert_large_workload,
    job_key,
)
from repro.core.calibration import (
    CALIBRATION_ENV,
    fit_corrections,
    reset_calibration_state,
    save_calibration,
)
from repro.core.macro import TPDCIM_MACRO
from repro.obs.profile import DevicePeaks
from repro.search import FIDELITIES, PortfolioSettings, SASettings
from repro.service.queue import _normalize_submit_args

#: the fit's roofline peaks, passed in (the CPU has no published peaks)
PEAKS = DevicePeaks(197e12, 819e9, "synthetic test peaks")
SMALL = DesignSpace(mr=(1, 2, 3), mc=(1, 2), scr=(1, 4, 16),
                    is_kb=(2, 16, 128), os_kb=(2, 16, 64))


def _job(objective="ee"):
    return ExploreJob(TPDCIM_MACRO, bert_large_workload(), 2.23,
                      objective=objective, space=SMALL,
                      search_method="portfolio")


def _synthetic_records(n: int = 8) -> list[dict]:
    pf, pb = PEAKS.flops, PEAKS.bw
    return [{"kernel": "cim_matmul", "bucket": f"b{i}", "tiling": "AF",
             "us": 2.0 * (1e9 * (i + 1)) / pf * 1e6
             + 0.5 * (1e6 * (n - i)) / pb * 1e6,
             "flops": 1e9 * (i + 1), "bytes": 1e6 * (n - i), "seed": 0}
            for i in range(n)]


@pytest.fixture
def pinned_artifact(tmp_path, monkeypatch):
    """A calibration artifact pinned via CIM_TUNER_CALIBRATION, so the
    measured rung never runs a live kernel sweep inside the test."""
    records = _synthetic_records()
    path = str(tmp_path / "calibration.json")
    save_calibration(path, fit_corrections(records, PEAKS),
                     records=records)
    monkeypatch.setenv(CALIBRATION_ENV, path)
    reset_calibration_state()
    yield path
    monkeypatch.delenv(CALIBRATION_ENV)
    reset_calibration_state()


# ------------------------------------------------------------------ #
# settings validation
# ------------------------------------------------------------------ #
def test_portfolio_settings_fidelity_validation():
    assert FIDELITIES == ("analytic", "measured")
    assert PortfolioSettings().fidelity == "analytic"
    assert PortfolioSettings(fidelity="measured").topk >= 1
    with pytest.raises(ValueError, match="fidelity"):
        PortfolioSettings(fidelity="quantum")
    with pytest.raises(ValueError, match="topk"):
        PortfolioSettings(topk=0)


# ------------------------------------------------------------------ #
# job-key separation
# ------------------------------------------------------------------ #
def test_job_key_separates_fidelities(pinned_artifact):
    job = _job()
    k_analytic = job_key(job, "portfolio", PortfolioSettings(seed=1))
    k_measured = job_key(job, "portfolio",
                         PortfolioSettings(seed=1, fidelity="measured"))
    assert k_analytic != k_measured, \
        "a warm analytic result must never answer a calibrated query"
    # analytic keys are calibration-independent: same key with no pin
    import os
    pin = os.environ.pop(CALIBRATION_ENV)
    reset_calibration_state()
    try:
        assert job_key(job, "portfolio",
                       PortfolioSettings(seed=1)) == k_analytic
    finally:
        os.environ[CALIBRATION_ENV] = pin
        reset_calibration_state()


# ------------------------------------------------------------------ #
# the measured rung
# ------------------------------------------------------------------ #
def test_measured_rung_reports_both_rankings(pinned_artifact):
    engine = ExplorationEngine()
    settings = PortfolioSettings(total_evals=3000, seed=1,
                                 fidelity="measured", topk=4)
    (res,) = engine.run([_job()], method="portfolio", settings=settings)
    assert res.search["portfolio"]["fidelity"] == "measured"
    tf = res.search["two_fidelity"]
    assert tf["source"] == "artifact"
    assert tf["measurement_count"] == 8
    assert -1.0 <= tf["rank_correlation"] <= 1.0
    n = tf["topk"]
    assert 1 <= n <= 4, "re-scored pool is capped at settings.topk"
    assert sorted(tf["analytic_ranking"]) == list(range(n))
    assert sorted(tf["measured_ranking"]) == list(range(n))
    assert len(tf["analytic_values"]) == len(tf["measured_values"]) == n
    # winners are config rows (mr, mc, scr, is, os) under each fidelity
    assert len(tf["analytic_winner"]) == len(tf["measured_winner"]) == 5
    assert tf["calibration_version"] != "uncalibrated"
    # analytic runs carry no two_fidelity payload
    (res_a,) = engine.run([_job()], method="portfolio",
                          settings=PortfolioSettings(total_evals=3000,
                                                     seed=1))
    assert res_a.search["portfolio"]["fidelity"] == "analytic"
    assert "two_fidelity" not in res_a.search


def test_measured_rung_replays_deterministically(pinned_artifact):
    settings = PortfolioSettings(total_evals=3000, seed=1,
                                 fidelity="measured", topk=4)
    runs = []
    for _ in range(2):
        (res,) = ExplorationEngine().run([_job()], method="portfolio",
                                         settings=settings)
        runs.append(res)
    a, b = runs
    assert a.config.as_tuple() == b.config.as_tuple()
    assert a.search["two_fidelity"] == b.search["two_fidelity"], \
        "pinned artifact + fixed seed must replay bit-for-bit"


# ------------------------------------------------------------------ #
# the unified submit contract
# ------------------------------------------------------------------ #
def test_normalize_submit_args_fidelity_aliases():
    job = _job()
    m, eff, key = _normalize_submit_args(job, method="portfolio",
                                         fidelity="two")
    assert m == "portfolio" and eff.fidelity == "measured"
    m2, eff2, key2 = _normalize_submit_args(job, method="portfolio",
                                            fidelity="measured")
    assert eff2.fidelity == "measured" and key2 == key
    # analytic (or omitted) leaves the settings untouched
    m3, eff3, key3 = _normalize_submit_args(job, method="portfolio")
    assert eff3.fidelity == "analytic" and key3 != key
    base = PortfolioSettings(seed=7)
    _, eff4, _ = _normalize_submit_args(job, method="portfolio",
                                        settings=base,
                                        fidelity="analytic")
    assert eff4 is base or eff4 == base


def test_normalize_submit_args_rejects_bad_fidelity():
    job = _job()
    with pytest.raises(ValueError, match="fidelity"):
        _normalize_submit_args(job, method="portfolio", fidelity="bogus")
    # backends without a fidelity axis reject non-analytic requests
    with pytest.raises(ValueError, match="fidelity"):
        _normalize_submit_args(job, method="sa", settings=SASettings(),
                               fidelity="measured")
    # ...but explicitly-analytic submissions pass through unchanged
    m, eff, _ = _normalize_submit_args(job, method="sa",
                                       settings=SASettings(),
                                       fidelity="analytic")
    assert m == "sa" and isinstance(eff, SASettings)


def test_fidelity_settings_replace_preserves_other_fields():
    base = PortfolioSettings(total_evals=1234, seed=9, topk=3)
    _, eff, _ = _normalize_submit_args(_job(), method="portfolio",
                                       settings=base, fidelity="two")
    assert eff.fidelity == "measured"
    assert eff.total_evals == 1234 and eff.seed == 9 and eff.topk == 3
    assert dataclasses.replace(eff, fidelity="analytic") == base
