"""bench.py helper tests — the pieces every bench result depends on, none
of which need a backend."""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from bench import peak_tflops  # noqa: E402


def test_peak_tflops_table_order_and_unknowns():
    assert peak_tflops("TPU v5 lite") == 197.0
    # v5p must match before the v5 substring does
    assert peak_tflops("TPU v5p") == 459.0
    assert peak_tflops("TPU v6e") == 918.0
    # a device that is not in the table is an error, not a silent None
    # (an MFU field that quietly disappears reads as "not computed")
    for kind in ("TFRT_CPU_0", "cpu", "TPU v9"):
        with pytest.raises(ValueError, match="PEAK_BF16_TFLOPS"):
            peak_tflops(kind)
