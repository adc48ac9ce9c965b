"""The look for a chip, shared by every entry point of the benchmark."""
from __future__ import annotations

import sys


def tpu_devices(n_chips: int = 1):
    """JAX's devices, or None (with the reason on standard error) when
    JAX finds no TPU or fewer than ``n_chips`` of them."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU found (JAX platform {devices[0].platform!r});"
              f" this benchmark has no CPU fallback", file=sys.stderr)
        return None
    if len(devices) < n_chips:
        print(f"bench: {n_chips} chips needed, JAX finds {len(devices)}",
              file=sys.stderr)
        return None
    return devices
