"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run driver must set
XLA_FLAGS=--xla_force_host_platform_device_count before first jax init.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the 'pod' axis is pure
data parallelism — the only cross-pod (DCN) collective is the once-per-step
gradient all-reduce.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    import math

    n = math.prod(shape)
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for the production mesh, have {len(devices)} — "
            "run under dryrun.py (it sets --xla_force_host_platform_device_count=512)"
        )
    return jax.make_mesh(
        shape, axes, devices=devices, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_smoke_mesh(devices=None):
    """Tiny mesh over whatever devices exist (tests)."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    model = 2 if n % 2 == 0 and n > 1 else 1
    return jax.make_mesh((n // model, model), ("data", "model"), devices=devices)
