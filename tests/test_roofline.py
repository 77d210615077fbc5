"""The peak table behind every roofline share: keyed by device kind,
sourced, and an error for a device it does not list."""

import pytest

from repro.analysis.roofline import PEAKS, peaks_for, score_eval_markdown


def test_v5e_row_is_sourced():
    v5e = peaks_for("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw, v5e.ici_bw) == (197e12, 819e9, 200e9)
    assert '"TPU v5e"' in v5e.source


@pytest.mark.parametrize("kind", ["cpu", None, "TPU v4"])
def test_unknown_device_raises(kind):
    assert kind not in PEAKS
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for(kind)


def test_score_eval_join_refuses_a_cpu_record():
    artifact = {"backend": "cpu", "device_kind": "cpu", "rows": []}
    with pytest.raises(KeyError, match="no published peaks"):
        score_eval_markdown(artifact)
    artifact["device_kind"] = "TPU v5 lite"
    assert "TPU v5 lite" in score_eval_markdown(artifact)
