"""The roofline's peaks come from one table keyed by device kind."""
import pytest

from benchmarks import roofline


def test_peaks_table_knows_v5e_and_refuses_unknown_kinds():
    v5e = roofline.peaks("TPU v5 lite")
    assert v5e["flops"] == 197e12 and v5e["hbm_bw"] == 819e9
    for kind in ("cpu", "TPU v4", ""):
        with pytest.raises(ValueError, match="no published peaks"):
            roofline.peaks(kind)


def test_dryrun_analysis_uses_the_compile_target_peaks():
    art = {"arch": "a", "shape": "s", "mesh": "single",
           "cost": {"flops": 197e12, "bytes accessed": 819e9}}
    row = roofline.analyze(art)
    assert row["compute_s"] == pytest.approx(1.0)
    assert row["memory_s"] == pytest.approx(1.0)
    with pytest.raises(ValueError):
        roofline.analyze(art, device_kind="cpu")
