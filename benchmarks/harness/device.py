"""The device a run is on: the check that it is a TPU, the benchmark's own
table of peaks, and the peak of device memory."""

from __future__ import annotations

# (dense bf16 peak FLOP/s, peak HBM bytes/s, HBM bytes) per chip, keyed by
# the `device_kind` jax reports.  Source: Google Cloud TPU documentation,
# "TPU v5e" system architecture page — 197 TFLOP/s bf16, 819 GB/s, 16 GB;
# jax reports a v5e chip as "TPU v5 lite".  This is the benchmark's OWN copy
# (the program has one in observe/cost.py): a change to the program cannot
# move the yardstick.  A device that is not listed is an error, never a
# default.
PEAKS_BY_DEVICE_KIND = {
    "TPU v5 lite": (197.0e12, 8.19e11, 16.0e9),
    "TPU v5e": (197.0e12, 8.19e11, 16.0e9),
}


class NoAccelerator(SystemExit):
    """The run is not on the chips its cell asks for: exit non-zero, print
    no result."""


class UnknownDeviceKind(LookupError):
    pass


def peaks(device_kind: str) -> tuple[float, float, float]:
    try:
        return PEAKS_BY_DEVICE_KIND[device_kind]
    except KeyError:
        raise UnknownDeviceKind(
            f"device kind {device_kind!r} is not in the benchmark's table of "
            "peaks (benchmarks/harness/device.py); add its datasheet row "
            "with its source") from None


def require_tpu(chips: int) -> dict:
    """The device as jax reports it; exits unless jax's devices are at
    least `chips` TPU chips.  The benchmark has no CPU mode."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu" or len(devs) < chips:
        raise NoAccelerator(
            f"benchmark: needs {chips} TPU chip(s); jax reports "
            f"{len(devs)} x {d0.platform} ({d0.device_kind})")
    peaks(d0.device_kind)
    return describe(devs)


def describe(devs) -> dict:
    return {"platform": str(devs[0].platform),
            "kind": str(devs[0].device_kind), "count": len(devs)}


def memory_peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest chip, as its allocator counts them
    (0 where the backend keeps no such count, as the CPU backend does not).
    On a TPU this is the peak of live ARRAYS: a program's own temporaries
    are not in it."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
