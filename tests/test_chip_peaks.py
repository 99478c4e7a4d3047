"""The peaks table (utils/flops.py::CHIP_PEAKS): keyed by jax's
device_kind, an unknown TPU an error, and no peak at all off a TPU — so
no CPU record carries an MFU against a chip's peak."""

import types

import jax
import pytest

from fms_fsdp_tpu.utils import flops


def _fake_devices(platform, kind):
    dev = types.SimpleNamespace(platform=platform, device_kind=kind)
    return lambda *a, **k: [dev]


@pytest.mark.parametrize(
    "kind,chip",
    [
        ("TPU v5 lite", "v5e"),  # what jax reports for a v5e
        ("TPU v5e", "v5e"),
        ("TPU v5p", "v5p"),
        ("TPU v4", "v4"),
        ("TPU v6 lite", "v6e"),
    ],
)
def test_device_kind_resolves(monkeypatch, kind, chip):
    monkeypatch.setattr(jax, "devices", _fake_devices("tpu", kind))
    assert flops.chip_from_device_kind(kind) == chip
    assert flops.chip_peaks() is flops.CHIP_PEAKS[chip]


def test_v5e_peaks(monkeypatch):
    monkeypatch.setattr(jax, "devices", _fake_devices("tpu", "TPU v5 lite"))
    assert flops.peak_flops_per_chip() == 197e12
    assert flops.chip_peaks()["hbm_bytes_per_s"] == 819e9


def test_unknown_tpu_kind_is_an_error(monkeypatch):
    monkeypatch.setattr(jax, "devices", _fake_devices("tpu", "TPU v9 mega"))
    with pytest.raises(ValueError, match="no published peaks"):
        flops.peak_flops_per_chip()
    # obs_chip_hint is the one override
    assert flops.peak_flops_per_chip("v5e") == 197e12


def test_cpu_has_no_peak():
    assert jax.devices()[0].platform == "cpu"
    assert flops.chip_peaks() is None
    assert flops.peak_flops_per_chip() is None
    # not even with a hint: the record was not measured on that chip
    assert flops.peak_flops_per_chip("v5e") is None
