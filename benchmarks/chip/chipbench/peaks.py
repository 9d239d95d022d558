"""Published peaks of the chips the benchmark runs on, keyed by
``jax.Device.device_kind``.  A kind missing here is an error, never a
default: a share of a peak read against the wrong chip means nothing."""

from __future__ import annotations

_V5E = {
    "peak_flops": 197e12,       # bf16 MXU FLOP/s
    "hbm_bw": 819e9,            # HBM B/s
    "hbm_bytes": 16e9,          # HBM per chip
    "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
              "16 GB HBM at 819 GB/s per chip (the v5e row of "
              "repro.hw, copied)",
}

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}") from None
