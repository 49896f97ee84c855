"""The device a run is on: found, checked against what the cell asks
for, and described for the result line."""

import sys


def require_chips(chips):
    """Leave with a non-zero code, printing no result, unless JAX runs
    on a TPU with at least ``chips`` chips. No fallback to a CPU."""
    import jax

    devices = jax.devices()
    if jax.default_backend() != "tpu" or len(devices) < chips:
        print(
            f"benchmark: needs {chips} TPU chip(s); JAX found "
            f"{len(devices)} x {devices[0].platform} "
            f"({devices[0].device_kind}); nothing was run",
            file=sys.stderr,
        )
        raise SystemExit(3)


def describe():
    """Platform, kind and count as JAX reports them."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def memory_peak_bytes():
    """Peak bytes in use on the fullest chip (0 where the backend does
    not report it, as on a CPU)."""
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
