"""Production meshes.  TPU v5e targets:
single pod = 16x16 = 256 chips (data, model);
multi-pod  = 2x16x16 = 512 chips (pod, data, model).

Functions, not module constants: importing this module never touches jax
device state (the dry-run must set XLA_FLAGS before the first jax init).
"""

from __future__ import annotations

import jax

from repro.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(*, multi_pod: bool = False, devices=None):
    """Reduced mesh over ``devices`` (default: every visible device).

    One device gives a ``(1, 1)`` (data, model) mesh: a single client
    with no model parallelism.
    """
    devices = list(jax.devices() if devices is None else devices)
    n = len(devices)
    if multi_pod:
        assert n % 2 == 0 and n >= 8, n
        shape = (2, 2, n // 4)
        axes = ("pod", "data", "model")
    elif n == 1:
        shape, axes = (1, 1), ("data", "model")
    else:
        assert n % 2 == 0, n
        shape = (2, n // 2)
        axes = ("data", "model")
    return make_mesh(shape, axes, devices=devices)


# v5e hardware constants for the roofline (per chip / per link)
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BW = 819e9                # B/s
ICI_BW = 50e9                 # B/s per link
