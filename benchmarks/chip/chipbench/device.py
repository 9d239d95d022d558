"""Which devices a run uses, and what it reports about them."""

from __future__ import annotations


class NoChipError(SystemExit):
    """No accelerator, or fewer chips than the cell asks for.  Exits
    non-zero and the run prints no result."""


def require(chips: int) -> list:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChipError(
            f"chipbench: no TPU found: JAX runs on {devs[0].platform!r} "
            f"({devs[0].device_kind}); the benchmark has no CPU fallback")
    if len(devs) < chips:
        raise NoChipError(f"chipbench: the cell needs {chips} TPU chips, "
                          f"found {len(devs)}")
    return devs[:chips]


def peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest device (0 where the backend keeps
    no such statistic, as the CPU does)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks, default=0))


def describe(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak_bytes(devs)}
