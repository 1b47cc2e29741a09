"""The table of peaks (`benchlib/device.py`) that the roofline readers
divide by: the published figures per chip of the one kind the cells run on,
and an error that names the table for a kind it does not hold."""

import pytest

from tests.benchmark import helpers

helpers.load_run()  # puts the benchmark's directory on sys.path
from benchlib import device  # noqa: E402


def test_the_v5e_peaks_are_the_published_figures():
    peaks = device.peaks("TPU v5 lite")
    assert peaks == {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                     "hbm_bytes": 16e9, "ici_bytes_per_s": 200e9}
    # the interchip bandwidth is published in bits: 1,600 Gbps a chip
    assert peaks["ici_bytes_per_s"] == 1600e9 / 8


def test_a_kind_the_table_does_not_hold_is_an_error_that_names_the_table():
    with pytest.raises(device.DeviceError, match="TPU v5 lite") as err:
        device.peaks("cpu")
    assert "'cpu'" in str(err.value)
