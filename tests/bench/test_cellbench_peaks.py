"""The benchmark's table of peaks: v5e as published, anything else refused."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "bench"))

from peaks import PEAKS, peaks_for  # noqa: E402


def test_v5e_peaks_as_published():
    p = peaks_for("TPU v5 lite")
    assert (p.hbm_bytes_per_s, p.bf16_flops_per_s, p.hbm_bytes,
            p.ici_bits_per_s) == (819e9, 197e12, 16e9, 1600e9)
    assert "TPU v5e" in p.source


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5e", "tpu", ""])
def test_unknown_device_kind_is_refused(kind):
    assert kind not in PEAKS
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for(kind)
