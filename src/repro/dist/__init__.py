"""Distribution layer: logical-axis sharding over JAX meshes.

``repro.dist`` is the scale-out substrate every model/launch module
programs against.  The core idea (borrowed from GSPMD-style logical
axis annotation) is that model code names *logical* axes ("batch",
"heads", "fsdp", ...) and a per-mesh rule table resolves them to
physical mesh axes — with divisibility fallbacks so the same model code
runs unsharded on one device and fully sharded on a 512-chip mesh.

Public API (see :mod:`repro.dist.sharding` for details):

* ``MeshContext``       — logical-axis -> mesh-axis resolution.
* ``use_mesh``          — context manager installing the active context.
* ``current``           — the active ``MeshContext`` (or ``None``).
* ``shard_act``         — activation sharding constraint (identity when
                          no mesh context is installed).
* ``logical_for_path``  — parameter-path -> logical axes rules.
* ``param_sharding_tree`` — param pytree -> ``NamedSharding`` pytree.
* ``make_mesh``         — every mesh of the repo, with ``Auto`` axes.
"""
from repro.dist.sharding import (
    MeshContext,
    current,
    logical_for_path,
    make_mesh,
    param_sharding_tree,
    shard_act,
    use_mesh,
)

__all__ = [
    "MeshContext",
    "current",
    "logical_for_path",
    "make_mesh",
    "param_sharding_tree",
    "shard_act",
    "use_mesh",
]
