"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (jax locks the device count on first backend init — the dry-run must
set XLA_FLAGS before any mesh is built).

Single pod: (data=16, model=16) — 256 chips (one v5e pod).
Multi-pod:  (pod=2, data=16, model=16) — 512 chips; the 'pod' axis carries
pure data parallelism over DCN/slow links (gradient all-reduce only, int8
compressible), while 'data'+'model' stay intra-pod on fast ICI.
"""
from __future__ import annotations

import jax


def make_auto_mesh(shape, axes):
    """A mesh whose axes are all ``Auto``: the model harness leaves sharding
    propagation to the compiler (GSPMD) and states shardings only at its
    boundaries.  ``jax.make_mesh`` defaults to ``Explicit`` axes, under
    which every gather and contraction over a sharded dim must state its
    output sharding."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU benchmarks)."""
    return make_auto_mesh((data, model), ("data", "model"))


# v5e hardware constants for the roofline (per chip)
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BW = 819e9                # B/s
ICI_BW_PER_LINK = 50e9        # B/s  (~per direction per link)
