"""The device as JAX reports it; no chip, no benchmark."""

from __future__ import annotations

import sys


def require_tpu(chips: int) -> dict:
    """With JAX_PLATFORMS unset jax only warns when the TPU fails to
    initialise and carries on on the CPU, so ask what it ended up with.
    Exits 1, with nothing on stdout, on anything but `chips` or more TPUs."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"benchmark: jax's default backend is {backend!r}, not 'tpu'; "
              f"there is no CPU mode", file=sys.stderr)
        raise SystemExit(1)
    n = len(jax.devices())
    if n < chips:
        print(f"benchmark: the cell needs {chips} chips, jax has {n}",
              file=sys.stderr)
        raise SystemExit(1)
    return describe()


def describe() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))
