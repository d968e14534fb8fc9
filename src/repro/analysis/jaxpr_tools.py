"""jax-level helpers shared by contracts and checks: canonical abstract
call signatures (recompile lint), donated-leaf inventories and compiled
units (donation lint), recursive jaxpr walks and ``pallas_call``
introspection (transfer + Pallas lints).

Split out of ``registry`` so declaring a contract stays import-light.
"""
from __future__ import annotations

import warnings
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax import api_util

from .registry import CompiledUnit

# ----------------------------- signatures --------------------------------


def _aval_of(x):
    return api_util.shaped_abstractify(x)


def canonical_signature(tree: Any) -> str:
    """Canonical abstract signature of an argument pytree.

    Two calls with equal signatures hit the same jit cache entry; any
    drift (shape, dtype, weak-type flag) is a retrace.  The weak-type
    bit is kept explicit (``|w1``/``|w0``) so the recompile check can
    attribute a signature split to weak-type promotion drift alone."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    parts = []
    for leaf in leaves:
        av = _aval_of(leaf)
        weak = "w1" if getattr(av, "weak_type", False) else "w0"
        parts.append(f"{av.dtype}{list(av.shape)}|{weak}")
    return f"{treedef}::" + ";".join(parts)


def strip_weak(sig: str) -> str:
    """Signature with the weak-type bits erased — if two signatures
    collide after stripping, they differ ONLY in weak typing."""
    return sig.replace("|w1", "|w?").replace("|w0", "|w?")


# --------------------------- donation helpers ----------------------------


def donated_leaves(
    args: Sequence[Any], donate_argnums: Sequence[int]
) -> List[Dict[str, Any]]:
    """Describe every leaf of the donated arguments: path, shape, dtype,
    nbytes.  Accepts arrays or ShapeDtypeStructs."""
    out: List[Dict[str, Any]] = []
    for i in donate_argnums:
        flat = jax.tree_util.tree_flatten_with_path(args[i])[0]
        for path, leaf in flat:
            av = _aval_of(leaf)
            nbytes = int(np.prod(av.shape, dtype=np.int64)) * av.dtype.itemsize
            out.append({
                "path": f"arg{i}{jax.tree_util.keystr(path)}",
                "shape": tuple(int(d) for d in av.shape),
                "dtype": str(av.dtype),
                "nbytes": nbytes,
            })
    return out


def compile_unit(
    label: str,
    jitted: Any,
    args: Sequence[Any],
    donate_argnums: Sequence[int] = (),
    donate_min_bytes: int = 0,
    shard_divisors: Tuple[int, ...] = (1,),
    collective_budget: Optional[Dict[str, int]] = None,
    **kwargs: Any,
) -> CompiledUnit:
    """Lower+compile an already-jitted callable and capture the
    artifacts the checks need: post-SPMD HLO text, the donated-leaf
    inventory, and any donation warnings XLA raised at compile time."""
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        compiled = jitted.lower(*args, **kwargs).compile()
    donation_warnings = [
        str(w.message) for w in caught
        if "donated" in str(w.message).lower()
    ]
    return CompiledUnit(
        label=label,
        hlo=compiled.as_text(),
        donated=donated_leaves(args, donate_argnums),
        donate_min_bytes=donate_min_bytes,
        shard_divisors=shard_divisors,
        compile_warnings=donation_warnings,
        collective_budget=collective_budget,
    )


def pytree_leaf_specs(tree: Any) -> List[Tuple[str, str, Tuple[int, ...]]]:
    """(path, dtype, shape) for every leaf of a pytree — the shape the
    precision check's :class:`~.registry.ExactnessGate` expects for
    ``pool_leaves``."""
    out: List[Tuple[str, str, Tuple[int, ...]]] = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        av = _aval_of(leaf)
        out.append((
            jax.tree_util.keystr(path), str(av.dtype),
            tuple(int(d) for d in av.shape),
        ))
    return out


# ----------------------------- jaxpr walking -----------------------------


def _subjaxprs(params: Dict[str, Any]) -> Iterator[Any]:
    for v in params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for item in vals:
            sub = getattr(item, "jaxpr", item)
            if hasattr(sub, "eqns"):
                yield sub


def iter_eqns(closed_jaxpr: Any) -> Iterator[Any]:
    """Every equation in a (closed) jaxpr, recursing through nested
    call/control-flow/pallas jaxprs."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _subjaxprs(eqn.params):
            yield from iter_eqns(sub)


def find_eqns(closed_jaxpr: Any, primitive_name: str) -> List[Any]:
    return [e for e in iter_eqns(closed_jaxpr)
            if e.primitive.name == primitive_name]


# --------------------------- pallas introspection ------------------------


def _block_dim(b: Any) -> int:
    """One entry of a ``BlockMapping.block_shape``: a ``Blocked`` size,
    or 1 for a ``Squeezed`` dim (one element, indexed like a block)."""
    if isinstance(b, (int, np.integer)):
        return int(b)
    size = getattr(b, "block_size", None)
    return 1 if size is None else int(size)


def pallas_call_specs(closed_jaxpr: Any) -> List[Dict[str, Any]]:
    """Extract, for every ``pallas_call`` reachable from the jaxpr, the
    grid, per-operand block shapes/array shapes/dtypes, the evaluable
    index maps, and the interpret flag.  Pure introspection — nothing
    here executes the kernel."""
    out: List[Dict[str, Any]] = []
    for eqn in find_eqns(closed_jaxpr, "pallas_call"):
        gm = eqn.params["grid_mapping"]
        grid = tuple(int(g) for g in gm.grid)
        operands = []
        for bm in gm.block_mappings:
            sd = bm.array_aval
            operands.append({
                "array_shape": tuple(int(d) for d in sd.shape),
                "dtype": str(sd.dtype),
                "block_shape": tuple(_block_dim(b) for b in bm.block_shape),
                "index_map_jaxpr": bm.index_map_jaxpr,
            })
        out.append({
            "name": getattr(gm, "name", None) or str(
                eqn.params.get("name_and_src_info", "pallas_call")
            ),
            "grid": grid,
            "operands": operands,
            "interpret": bool(eqn.params.get("interpret", False)),
        })
    return out


def eval_index_map(index_map_jaxpr: Any, grid_idx: Sequence[int]) -> Tuple[int, ...]:
    """Evaluate one BlockSpec index map at a concrete grid point,
    returning the block indices it selects."""
    res = jax.core.eval_jaxpr(
        index_map_jaxpr.jaxpr, index_map_jaxpr.consts,
        *[int(i) for i in grid_idx],
    )
    return tuple(int(r) for r in res)
