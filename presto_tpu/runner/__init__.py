"""Query runners (reference: presto-main testing/LocalQueryRunner.java:236
— the single-process full-SQL harness the whole test pyramid keys off)."""

from presto_tpu.runner.local import (
    LocalRunner, MaterializedResult, Session, CatalogManager, QueryError,
)
from presto_tpu.runner.mesh import MeshRunner


def runner_for(catalog: str = "tpch", schema: str = "tiny",
               properties=None, access_control=None):
    """The runner a deployment's properties ask for, the one way to ask
    for a mesh: `mesh_devices` above 1 gives a MeshRunner over the first
    that many of jax.devices() (one worker task per chip), else the
    one-chip LocalRunner. More chips than are visible is make_mesh's
    ValueError, which names both counts: never a silent fall back to
    fewer."""
    from presto_tpu.session_properties import get_property
    properties = dict(properties or {})
    n = int(get_property(properties, "mesh_devices"))
    if n <= 1:
        return LocalRunner(catalog, schema, properties,
                           access_control=access_control)
    from presto_tpu.parallel.mesh import make_mesh
    return MeshRunner(catalog, schema, properties, mesh=make_mesh(n),
                      access_control=access_control)
