"""scripts/util_report.py calibration: no reported utilization fraction
may exceed 1.0 (ROADMAP hygiene rider), the clamp is monotone (a 1.05
reading means "at the ceiling", not a collapse to near zero), and the
raw value stays auditable via raw_frac."""

import importlib.util
from pathlib import Path

import pytest

pytestmark = pytest.mark.analysis


def _load_util_report():
    # main() is __main__-guarded, so a plain import defines
    # calibrated_fraction without running any benchmark
    path = Path(__file__).resolve().parents[1] / "scripts" / "util_report.py"
    spec = importlib.util.spec_from_file_location("_util_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


UR = _load_util_report()


def test_physical_fraction_passes_through():
    out = UR.calibrated_fraction(100.0, 1.0, 1000.0)
    assert out == {"frac": 0.1, "raw_frac": 0.1, "calibration": "per_iter"}


def test_over_peak_estimate_is_clamped_to_one():
    # raw = 5.0 > 1: physically impossible — report the ceiling, keep
    # the raw value for the audit trail
    out = UR.calibrated_fraction(5000.0, 1.0, 1000.0)
    assert out["calibration"] == "clamped"
    assert out["raw_frac"] == 5.0
    assert out["frac"] == 1.0


def test_clamp_is_monotone_across_the_peak_boundary():
    # 0.999 and 1.001 raw readings of the same workload must stay
    # adjacent (0.999 vs 1.0), not collapse by orders of magnitude
    just_under = UR.calibrated_fraction(999.0, 1.0, 1000.0)
    just_over = UR.calibrated_fraction(1001.0, 1.0, 1000.0)
    assert just_under["frac"] == pytest.approx(0.999)
    assert just_over["frac"] == 1.0
    assert just_over["frac"] >= just_under["frac"]


def test_no_data_cases():
    assert UR.calibrated_fraction(0.0, 1.0, 1000.0)["frac"] is None
    assert UR.calibrated_fraction(10.0, 0.0, 1000.0)["frac"] is None


def test_default_output_is_an_ignored_run_artifact():
    # a utilization report is a run artifact: by default it lands in the
    # ignored chiprun_out/ directory, never in a tracked root-level file
    root = Path(__file__).resolve().parents[1]
    text = (root / "scripts" / "util_report.py").read_text()
    assert '"chiprun_out"' in text and "UTIL_r0" not in text
    assert "chiprun_out/" in (root / ".gitignore").read_text().split()


@pytest.mark.parametrize(
    "est,wall,peak",
    [
        (1e18, 1e-6, 394e12),
        (1e9, 1e-3, 819e9),
        (3.5, 7.0, 1.0),
        (819e9, 1.0, 819e9),
    ],
)
def test_fraction_never_exceeds_one(est, wall, peak):
    out = UR.calibrated_fraction(est, wall, peak)
    assert out["frac"] is not None and 0.0 <= out["frac"] <= 1.0
