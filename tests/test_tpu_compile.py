"""Compile the main path's device programs for a described TPU v5e.

No chip is needed: the TPU compiler is installed and compiles for a
``v5e:2x2`` topology that is described, not attached.  What it refuses here
(an untileable block, a primitive Mosaic does not lower, a program that
does not fit) it would refuse on the chip.  The topology, and everything
built from it, lives in module fixtures, so importing this file never
touches the TPU library.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core import ticketing as tk
from repro.core import updates as up
from repro.core.hashing import table_capacity

MORSEL_ROWS = 4096
CHUNK_MORSELS = 256  # one 1M-row chunk


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.asarray(topo.devices).reshape(4), ("data",))


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize(
    "update,max_groups", [("scatter", 1 << 23), ("onehot", 2048)]
)
def test_consume_scan_compiles_for_v5e(one_chip, update, max_groups):
    """The XLA scan pipeline (the route every TPU plan takes) at real
    widths: a 1M-row chunk of 4096-row morsels, COUNT + SUM."""
    from repro.engine.groupby import _consume_scan

    cap = table_capacity(max_groups)
    table = tk.TicketTable(
        keys=_sds((cap,), jnp.uint32, one_chip),
        tickets=_sds((cap,), jnp.int32, one_chip),
        key_by_ticket=_sds((max_groups,), jnp.uint32, one_chip),
        count=_sds((), jnp.int32, one_chip),
        overflowed=_sds((), jnp.bool_, one_chip),
    )
    specs = ((None, "count"), ("qty", "sum"))
    state = up.AggState(specs, tuple(
        _sds((max_groups,), jnp.float32, one_chip) for _ in specs
    ))
    km = _sds((CHUNK_MORSELS, MORSEL_ROWS), jnp.uint32, one_chip)
    vm = {"qty": _sds((CHUNK_MORSELS, MORSEL_ROWS), jnp.float32, one_chip)}
    start = _sds((), jnp.int32, one_chip)
    compiled = _consume_scan.lower(
        table, state, km, vm, start, update_fn=up.get_update_fn(update),
        load_factor=0.5, checked=True, grow_bound=True,
    ).compile()
    assert compiled.memory_analysis().peak_memory_in_bytes < 16 * 2**30


@pytest.mark.parametrize("kind", ["sum", "max"])
def test_scan_body_onehot_kernel_compiles_for_v5e(one_chip, kind):
    """The re-laid-out segment kernel (morsel as (M/128, 128)) over 64
    morsels — the grid that broke the 8x128 block rule before."""
    from repro.kernels.segment_agg import segment_agg_pallas

    n = 64 * 1024
    f = functools.partial(
        segment_agg_pallas, num_groups=2048, kind=kind, strategy="onehot",
        morsel_size=1024, interpret=False,
    )
    compiled = jax.jit(f).lower(
        _sds((n,), jnp.int32, one_chip), _sds((n,), jnp.float32, one_chip)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_consume_step_compiles_on_4_chip_mesh(mesh4):
    """The per-chunk sharded consume step (checked, §4.4 pauses) over a
    mesh of four v5e chips."""
    from repro.core.distributed import ShardedCarry, make_sharded_consume_step

    max_local, ndev = 1 << 20, 4
    cap = table_capacity(max_local)
    row = NamedSharding(mesh4, P("data"))
    mat = NamedSharding(mesh4, P("data", None))
    cube = NamedSharding(mesh4, P("data", None, None))
    specs = ((None, "count"), ("qty", "sum"))
    carry = ShardedCarry(
        keys=_sds((ndev, cap), jnp.uint32, mat),
        tickets=_sds((ndev, cap), jnp.int32, mat),
        kbt=_sds((ndev, max_local), jnp.uint32, mat),
        count=_sds((ndev,), jnp.int32, row),
        ovf=_sds((ndev,), jnp.bool_, row),
        acc=up.AggState(specs, tuple(
            _sds((ndev, max_local), jnp.float32, mat) for _ in specs
        )),
    )
    nm = CHUNK_MORSELS // ndev
    km = _sds((ndev, nm, MORSEL_ROWS), jnp.uint32, cube)
    vm = {"qty": _sds((ndev, nm, MORSEL_ROWS), jnp.float32, cube)}
    start = _sds((ndev,), jnp.int32, row)
    step = make_sharded_consume_step(
        mesh4, "data", update="scatter", load_factor=0.5, checked=True,
    )
    compiled = jax.jit(step).lower(carry, km, vm, start).compile()
    per_device = compiled.memory_analysis().peak_memory_in_bytes
    assert 0 < per_device < 16 * 2**30
