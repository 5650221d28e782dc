"""Q18's inner aggregate over a lineitem whose rows are split over a mesh
of ``cfg["chips"]`` devices, on the axis ``cfg["mesh_axis"]``.  A test
configuration of the harness's multi-chip path, in no cell: the query,
answer, reference, control and comparison are ``tpch_q18agg_sf1``'s."""
import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from chipbench.configs.tpch_q18agg_sf1 import (  # noqa: F401
    PLAN, READS, answer, bytes_per_query, compare, control, prepare, reference)
from chipbench.data import tpch_lineitem as L


def make_data(cfg, seed: int) -> dict:
    mesh = Mesh(np.array(jax.devices()[:cfg["chips"]]), (cfg["mesh_axis"],))
    return L.generate(seed, orders=cfg["orders"], lineitems=cfg["lineitems"],
                      parts=cfg["parts"], suppliers=cfg["suppliers"],
                      sharding=NamedSharding(mesh, P(cfg["mesh_axis"])))
