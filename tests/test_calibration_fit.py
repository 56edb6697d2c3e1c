"""Calibration fitting pass: synthetic round-trip recovery, held-out
generalization, artifact save/load, version stability, and the pinned-env
CostModel resolution.  No JAX work -- measurement records are hand-built
from the documented MeasurementRecord schema."""
from __future__ import annotations

import dataclasses
import math

import pytest

from repro.core.calibration import (
    DEFAULT_TECH,
    CALIBRATION_ENV,
    CorrectionFactors,
    CostModel,
    calibration_version,
    default_cost_model,
    evaluate_corrections,
    fit_corrections,
    fit_report,
    load_calibration,
    reset_calibration_state,
    resolve_tech,
    save_calibration,
)
from repro.obs import profile

#: the fit's roofline peaks, passed in (the CPU has no published peaks)
PEAKS = profile.DevicePeaks(197e12, 819e9, "synthetic test peaks")


@pytest.fixture(autouse=True)
def _fresh_calibration(monkeypatch):
    """Each test sees no pinned artifact and no cached live fit."""
    monkeypatch.delenv(CALIBRATION_ENV, raising=False)
    reset_calibration_state()
    yield
    reset_calibration_state()


def _synthetic_records(compute: float, memory: float, n: int = 12,
                       noise: float = 0.0) -> list[dict]:
    """Records whose timings follow the fit model with KNOWN factors.

    flops:bytes ratios are spread out so the two roofline features are
    far from collinear and the joint 2x2 solve is well conditioned."""
    pf, pb = PEAKS.flops, PEAKS.bw
    records = []
    for i in range(n):
        flops = 1e9 * (i + 1)
        nbytes = 1e6 * (n - i)
        t_c = flops / pf * 1e6
        t_m = nbytes / pb * 1e6
        us = compute * t_c + memory * t_m
        if noise:
            us *= 1.0 + noise * ((-1) ** i)     # deterministic "noise"
        records.append({"kernel": "cim_matmul", "bucket": f"b{i}",
                        "tiling": "AF", "us": us, "flops": flops,
                        "bytes": nbytes, "seed": 0})
    return records


def test_fit_recovers_known_distortion():
    cf = fit_corrections(_synthetic_records(compute=3.7, memory=0.4), PEAKS)
    assert cf.compute == pytest.approx(3.7, rel=1e-6)
    assert cf.memory == pytest.approx(0.4, rel=1e-6)
    assert cf.update == cf.memory, "update must ride the memory term"
    assert cf.leakage == 1.0, "microbench cannot observe static power"
    assert cf.fitted_on == 12
    assert cf.residual_us == pytest.approx(0.0, abs=1e-6)


def test_fit_survives_noise_and_clamps():
    cf = fit_corrections(_synthetic_records(2.0, 5.0, noise=0.1), PEAKS)
    assert cf.compute == pytest.approx(2.0, rel=0.35)
    assert cf.memory == pytest.approx(5.0, rel=0.35)
    assert cf.residual_us > 0.0
    # absurd distortions clamp to the documented [1e-3, 1e3] range
    big = fit_corrections(_synthetic_records(1e9, 1e9), PEAKS)
    assert big.compute <= 1e3 and big.memory <= 1e3


def test_fit_raises_without_cost_analysis():
    bad = [{"kernel": "k", "bucket": "b", "tiling": "t", "us": 1.0,
            "flops": None, "bytes": None, "seed": 0}]
    with pytest.raises(ValueError, match="no usable measurement"):
        fit_corrections(bad, PEAKS)


def test_held_out_error_strictly_below_uncalibrated():
    records = _synthetic_records(4.0, 0.25, n=16, noise=0.05)
    rep = fit_report(records, holdout_fraction=0.25, seed=3, peaks=PEAKS)
    assert rep["holdout_records"] >= 1
    assert rep["train_records"] + rep["holdout_records"] == len(records)
    assert rep["calibrated_rms_us"] < rep["uncalibrated_rms_us"], \
        "fitted model must beat the identity model on records it never saw"
    assert rep["improvement"] > 1.0
    # the report's factors match a direct fit on the same train split
    cal = evaluate_corrections(records, fit_corrections(records, PEAKS),
                               PEAKS)
    assert cal <= evaluate_corrections(records, None, PEAKS)


def test_version_stable_and_content_addressed():
    a = fit_corrections(_synthetic_records(3.0, 0.5), PEAKS)
    b = fit_corrections(_synthetic_records(3.0, 0.5), PEAKS)
    c = fit_corrections(_synthetic_records(3.1, 0.5), PEAKS)
    assert calibration_version(a) == calibration_version(b)
    assert calibration_version(a) != calibration_version(c)
    assert calibration_version(None) == "uncalibrated"
    assert calibration_version(CorrectionFactors()) == "uncalibrated"


def test_artifact_round_trip(tmp_path):
    records = _synthetic_records(2.5, 0.8)
    cf = fit_corrections(records, PEAKS)
    path = str(tmp_path / "calibration.json")
    payload = save_calibration(path, cf, records=records,
                               report=fit_report(records, peaks=PEAKS))
    loaded, raw = load_calibration(path)
    assert loaded == cf
    assert raw["version"] == payload["version"] == calibration_version(cf)
    assert len(raw["measurements"]) == len(records)
    assert raw["report"]["improvement"] > 0.0


def test_with_corrections_touches_energy_not_area():
    cf = CorrectionFactors(compute=2.0, memory=3.0, update=4.0)
    tech = DEFAULT_TECH.with_corrections(cf)
    assert tech.e_mac_pj == DEFAULT_TECH.e_mac_pj * 2.0
    assert tech.e_sram_rd_pj_bit == DEFAULT_TECH.e_sram_rd_pj_bit * 3.0
    assert tech.e_ema_pj_bit == DEFAULT_TECH.e_ema_pj_bit * 3.0
    assert tech.e_cim_update_pj_bit == \
        DEFAULT_TECH.e_cim_update_pj_bit * 4.0
    # area and frequency are fidelity-invariant by design
    assert tech.a_cell_um2_bit == DEFAULT_TECH.a_cell_um2_bit
    assert tech.a_cu_um2 == DEFAULT_TECH.a_cu_um2
    assert tech.freq_mhz == DEFAULT_TECH.freq_mhz
    # identity corrections are bit-exact no-ops (same object)
    assert DEFAULT_TECH.with_corrections(None) is DEFAULT_TECH
    assert DEFAULT_TECH.with_corrections(CorrectionFactors()) is DEFAULT_TECH


def test_cost_model_facade_resolution():
    analytic = CostModel()
    assert analytic.tech is DEFAULT_TECH and not analytic.calibrated
    assert analytic.version == "uncalibrated"
    cf = CorrectionFactors(compute=2.0, memory=2.0, update=2.0)
    measured = CostModel(corrections=cf)
    assert measured.calibrated
    assert measured.version == calibration_version(cf)
    assert measured.tech.e_mac_pj == DEFAULT_TECH.e_mac_pj * 2.0
    assert resolve_tech(None) is DEFAULT_TECH
    custom = dataclasses.replace(DEFAULT_TECH, freq_mhz=1000.0)
    assert resolve_tech(custom) is custom


def test_default_cost_model_follows_env_pin(tmp_path, monkeypatch):
    assert not default_cost_model().calibrated
    records = _synthetic_records(3.0, 0.5)
    path = str(tmp_path / "cal.json")
    save_calibration(path, fit_corrections(records, PEAKS), records=records)
    monkeypatch.setenv(CALIBRATION_ENV, path)
    reset_calibration_state()               # env changed -> re-resolve
    cm = default_cost_model()
    assert cm.calibrated
    assert cm.version == calibration_version(fit_corrections(records, PEAKS))
    assert math.isfinite(cm.tech.e_mac_pj)
    monkeypatch.delenv(CALIBRATION_ENV)
    reset_calibration_state()
    assert not default_cost_model().calibrated
