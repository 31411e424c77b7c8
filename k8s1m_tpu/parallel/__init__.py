from k8s1m_tpu.parallel.mesh import (
    auto_mesh_shape,
    batch_specs,
    make_mesh,
    parse_mesh,
    resolve_mesh,
    table_specs,
)
from k8s1m_tpu.parallel.sharded_cycle import (
    make_sharded_packed_step,
    make_sharded_step,
)

__all__ = [
    "auto_mesh_shape",
    "make_mesh",
    "parse_mesh",
    "resolve_mesh",
    "table_specs",
    "batch_specs",
    "make_sharded_step",
    "make_sharded_packed_step",
]
