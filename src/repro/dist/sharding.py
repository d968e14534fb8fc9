"""Logical-axis sharding: the resolution layer between model code and
physical meshes.

Model code annotates activations with *logical* axis names::

    x = shard_act(x, ("batch", "seq_sp", None))

and parameters are matched by path against :data:`PARAM_RULES`::

    logical_for_path("blocks/0/mixer/wq/w", 2)  ->  ("fsdp", "tp")

A :class:`MeshContext` resolves logical names to physical mesh axes via
a rule table (``logical -> tuple of mesh axes``), with two fallbacks
that let identical model code run on any mesh:

* **divisibility** — a dim that is not divisible by the resolved axis
  size replicates (``axes_for`` returns ``None``); for multi-axis rules
  the longest divisible *prefix* wins (e.g. ``batch -> ("pod", "data")``
  degrades to ``("pod",)`` and then to replicated).
* **each mesh axis used at most once per spec** — a later dim whose rule
  names an already-consumed axis replicates on that axis instead.

With no installed context (``use_mesh`` not entered) every annotation is
an exact no-op, so all model code runs unsharded by default — this is
what lets the serving stack (``repro.serve``: bucketed Engine, paged
continuous-batching Scheduler) and the CPU test suite run the exact
same model code that shards on a production mesh.  The DSE side reuses
the same logical axes for its 2-D scenario x island meshes
(``core.explorer.run_islands_multi``), and ``mamba``'s fused Pallas
scan wraps itself in ``repro.dist.compat.shard_map`` with specs
resolved through the active context.  See docs/architecture.md.
"""
from __future__ import annotations

import contextlib
import re
import threading
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

Axes = Tuple[str, ...]
LogicalDims = Sequence[Optional[str]]

# Mesh axes that carry data parallelism, in nesting order (outermost
# first).  A 3-axis production mesh is ("pod", "data", "model"); a
# single pod drops "pod".
_DATA_AXES = ("pod", "data")
_MODEL_AXES = ("model",)

# Logical names that resolve to the tensor-parallel ("model") axis.
_MODEL_LOGICAL = (
    "tp", "heads", "kv_heads", "ff", "d_inner", "experts", "vocab", "seq_sp",
)


def make_mesh(shape: Sequence[int], axes: Sequence[str], devices=None) -> Mesh:
    """Every mesh of the repo.  Its axes are ``Auto``: the rules here
    place arrays with ``with_sharding_constraint`` and leave the
    propagation to GSPMD, which ``jax.make_mesh``'s default ``Explicit``
    axes refuse."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes),
        devices=devices,
    )


def default_rules(mesh: Mesh) -> Dict[str, Axes]:
    """Default logical->physical rules derived from the mesh axis names.

    ``batch`` (and ``fsdp``) map to every data-like axis present, in mesh
    order — on a 3-axis mesh that is the multi-axis rule
    ``("pod", "data")`` with prefix fallback handled at resolution time.
    """
    names = tuple(mesh.axis_names)
    data = tuple(a for a in _DATA_AXES if a in names)
    model = tuple(a for a in _MODEL_AXES if a in names)
    rules: Dict[str, Axes] = {"batch": data, "fsdp": data}
    for logical in _MODEL_LOGICAL:
        rules[logical] = model
    return rules


class MeshContext:
    """Resolves logical axis names against one physical mesh.

    ``serving=True`` marks a *serving* context (:func:`serving_context`):
    :func:`repl_act` gathers an activation back to replicated,
    :func:`take_rows` looks rows up in a row-sharded table, and the
    row-parallel projections (``models.common.dense_row_parallel``) add
    their partial sums.  Training contexts leave it ``False``; there
    :func:`repl_act` is a no-op and GSPMD places every collective.
    """

    def __init__(self, mesh: Mesh, rules: Optional[Dict[str, Axes]] = None,
                 serving: bool = False):
        self.mesh = mesh
        self.rules = dict(rules) if rules is not None else default_rules(mesh)
        self.serving = bool(serving)

    # -- resolution -----------------------------------------------------------
    def _axis_size(self, axis: str) -> int:
        # Mesh.shape (name -> size) also exists on AbstractMesh, which has
        # no .devices — required for dry-runs over abstract meshes.
        return self.mesh.shape[axis]

    def _divisible_prefix(self, axes: Axes, dim: int) -> Axes:
        """Longest prefix of ``axes`` whose total size divides ``dim``."""
        for end in range(len(axes), 0, -1):
            size = 1
            for a in axes[:end]:
                size *= self._axis_size(a)
            if dim % size == 0:
                return axes[:end]
        return ()

    def axes_for(self, logical: str, dim: int) -> Optional[Axes]:
        """Mesh axes for one logical dim, or ``None`` -> replicate.

        ``None`` when the logical name has no rule, the rule names axes
        absent from this mesh, or ``dim`` is not divisible by the axis
        size (longest-divisible-prefix fallback for multi-axis rules).
        """
        axes = self.rules.get(logical)
        if not axes:
            return None
        axes = tuple(a for a in axes if a in self.mesh.axis_names)
        return self._divisible_prefix(axes, dim) or None

    def spec(self, logical_dims: LogicalDims, shape: Sequence[int]) -> P:
        """Resolve per-dim logical names into a ``PartitionSpec``.

        Raises ``ValueError`` on rank mismatch.  Each mesh axis is used
        at most once; a dim whose axes were already consumed replicates.
        """
        if len(logical_dims) != len(shape):
            raise ValueError(
                f"rank mismatch: {len(logical_dims)} logical dims "
                f"{tuple(logical_dims)} for shape {tuple(shape)}"
            )
        used: set = set()
        entries = []
        for logical, dim in zip(logical_dims, shape):
            axes = None if logical is None else self.axes_for(logical, dim)
            if axes:
                axes = tuple(a for a in axes if a not in used)
                if axes:
                    axes = self._divisible_prefix(axes, dim)
            if not axes:
                entries.append(None)
                continue
            used.update(axes)
            entries.append(axes[0] if len(axes) == 1 else axes)
        return P(*entries)

    def sharding(self, logical_dims: LogicalDims, shape: Sequence[int]) -> NamedSharding:
        # Canonicalise by dropping trailing replicated dims: jit emits
        # output shardings in this canonical form, and NamedSharding
        # equality is structural, so a device_put placement built with
        # the full-rank spec would MISS the jit cache the first time a
        # program sees a jit-produced array in that slot (one spurious
        # recompile per program whose first call saw the fresh pool).
        entries = tuple(self.spec(logical_dims, shape))
        while entries and entries[-1] is None:
            entries = entries[:-1]
        return NamedSharding(self.mesh, P(*entries))


# ------------------------------ active context --------------------------------
class _ContextStack(threading.local):
    def __init__(self):
        self.stack = []


_ACTIVE = _ContextStack()


def current() -> Optional[MeshContext]:
    """The innermost active :class:`MeshContext`, or ``None``."""
    return _ACTIVE.stack[-1] if _ACTIVE.stack else None


@contextlib.contextmanager
def use_mesh(mesh, rules: Optional[Dict[str, Axes]] = None):
    """Install ``mesh`` (or an existing ``MeshContext``) as the active
    context consumed by :func:`shard_act` / :func:`current`."""
    ctx = mesh if isinstance(mesh, MeshContext) else MeshContext(mesh, rules)
    _ACTIVE.stack.append(ctx)
    try:
        yield ctx
    finally:
        _ACTIVE.stack.pop()


def shard_act(x, logical_dims: LogicalDims):
    """Constrain ``x`` to the active context's resolution of
    ``logical_dims``; exact identity no-op when no context is installed."""
    ctx = current()
    if ctx is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, ctx.sharding(logical_dims, x.shape)
    )


def repl_act(x):
    """Constrain ``x`` to fully replicated under a serving context;
    identity otherwise.

    Model code calls it where every device needs the whole value: the
    vocab-sharded logits that sampling reads (an all-gather), the MoE
    combine over expert-sharded outputs, and the output of a row-parallel
    projection, whose partial sums the constraint turns into one
    all-reduce.
    """
    ctx = current()
    if ctx is None or not ctx.serving:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, P())
    )


def take_rows(table, ids):
    """``table[ids]`` for an embedding table.  Under a serving context
    whose ``vocab`` rule splits the table's rows over the mesh, each
    device looks the ids up in the rows it holds, writes zeros for ids
    outside them, and one all-reduce (named scope ``repro.tp.embed``)
    adds the devices' rows: exact in any dtype, since one device gives
    each row.  The table is never gathered whole."""
    ctx = current()
    axes = (ctx.axes_for("vocab", table.shape[0])
            if ctx is not None and ctx.serving else None)
    if not axes:
        return table[ids]
    axis = axes[0] if len(axes) == 1 else axes

    def local(tab, idx):
        n = tab.shape[0]
        rel = idx - jax.lax.axis_index(axis) * n
        held = (rel >= 0) & (rel < n)
        rows = tab[jnp.where(held, rel, 0)]
        rows = jnp.where(held[..., None], rows, jnp.zeros((), rows.dtype))
        return jax.lax.psum(rows, axis)

    with jax.named_scope("repro.tp.embed"):
        return jax.shard_map(
            local, mesh=ctx.mesh, in_specs=(P(axis), P()), out_specs=P(),
            check_vma=False,
        )(table, ids)


# ------------------------------ parameter rules --------------------------------
def _path_str(path) -> str:
    """jax key-path -> "a/b/0/c" string (DictKey/SequenceKey/GetAttrKey)."""
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:  # pragma: no cover - future key types
            parts.append(str(p))
    return "/".join(parts)


# Ordered (pattern, base logical axes) — first match wins.  ``base`` is
# the logical layout at the parameter's natural rank; a scan-stacked
# leaf (rank + 1, stacked over layer groups) gets a leading ``None``.
PARAM_RULES: Tuple[Tuple[str, Tuple[Optional[str], ...]], ...] = (
    # attention projections (GQA + MLA low-rank factors)
    (r"(?:^|/)(?:wq|wk|wv|q_a|q_b|kv_a|kv_b)/w$", ("fsdp", "tp")),
    (r"(?:^|/)wo/w$", ("tp", "fsdp")),
    # dense FFN (leaf dicts with /w) — includes MoE shared experts
    (r"(?:^|/)(?:w_up|w_gate)/w$", ("fsdp", "ff")),
    (r"(?:^|/)w_down/w$", ("ff", "fsdp")),
    # MoE expert banks: (E, d_model, d_ff) / (E, d_ff, d_model) — E on
    # the model axis, d_ff on the data axes (fully sharded, §Perf I6)
    (r"(?:^|/)(?:w_gate|w_up)$", ("experts", None, "fsdp")),
    (r"(?:^|/)w_down$", ("experts", "fsdp", None)),
    (r"(?:^|/)router/w$", ("fsdp", None)),
    # embedding / unembedding
    (r"(?:^|/)embed/w$", ("vocab", "fsdp")),
    (r"(?:^|/)head/w$", ("fsdp", "vocab")),
    # mamba mixer
    (r"(?:^|/)in_proj/w$", ("fsdp", "tp")),
    (r"(?:^|/)x_proj/w$", ("tp", None)),
    (r"(?:^|/)dt_proj/w$", (None, "tp")),
    (r"(?:^|/)out_proj/w$", ("tp", "fsdp")),
    (r"(?:^|/)conv_w$", ("tp", None)),
    (r"(?:^|/)A_log$", ("tp", None)),
    # MTP combiner
    (r"(?:^|/)proj/w$", ("fsdp", None)),
)
_PARAM_RULES = tuple((re.compile(pat), base) for pat, base in PARAM_RULES)


def logical_for_path(path: str, ndim: int) -> Tuple[Optional[str], ...]:
    """Logical axes for a parameter path at a given rank.

    Unmatched paths — norms, biases, raw optimizer-moment paths like
    ``.../w_gate/m`` (the caller strips moment suffixes first, see
    ``launch.dryrun.state_shardings``) — replicate.  A matched rule with
    an unreconcilable rank also replicates.
    """
    for pat, base in _PARAM_RULES:
        if pat.search(path):
            if ndim == len(base):
                return tuple(base)
            if ndim == len(base) + 1:  # scan-stacked over layer groups
                return (None,) + tuple(base)
            break
    return (None,) * ndim


def param_sharding_tree(shape_tree, mesh: Mesh, rules: Optional[Dict[str, Axes]] = None):
    """Map :func:`logical_for_path` over a param (shape) pytree into
    ``NamedSharding``s on ``mesh`` — the ``device_put`` layout for a
    freshly-initialized model and the dry-run's param shardings."""
    ctx = MeshContext(mesh, rules)

    def one(path, leaf):
        logical = logical_for_path(_path_str(path), len(leaf.shape))
        return ctx.sharding(logical, leaf.shape)

    return jax.tree_util.tree_map_with_path(one, shape_tree)


# ------------------------------ serving rules ----------------------------------
# Logical names a *serving* mesh resolves.  ``tp``/``d_inner``/``batch``/
# ``seq_sp``/``fsdp`` are deliberately absent: every annotation that names
# them resolves to replicated under a serving context (one replica's
# batch is never split, and the mamba and MLA contractions stay whole).
_SERVE_MODEL_LOGICAL = ("heads", "kv_heads", "ff", "experts", "vocab")


def serve_rules(mesh: Mesh) -> Dict[str, Axes]:
    """Logical->physical rules for a tensor/expert-parallel serving mesh
    (axis name ``"model"``)."""
    names = tuple(mesh.axis_names)
    model = tuple(a for a in _MODEL_AXES if a in names)
    return {logical: model for logical in _SERVE_MODEL_LOGICAL}


def serving_context(mesh: Mesh) -> MeshContext:
    """The serving :class:`MeshContext` for ``mesh``."""
    return MeshContext(mesh, rules=serve_rules(mesh), serving=True)


# Serving parameter layout (ordered, first match wins; unmatched ->
# replicate): Megatron-style tensor parallelism.  Column-parallel
# ``wq``/``wk``/``wv`` and ``w_up``/``w_gate`` split their outputs (heads,
# ff columns); row-parallel ``wo`` and dense ``w_down`` split their input
# rows the same way, so the activation between the two never leaves its
# device, and each row-parallel product ends in one all-reduce of partial
# sums (``models.common.dense_row_parallel``).  The embedding and the LM
# head split the vocabulary (``take_rows``; the logits are gathered for
# sampling).  Replicated, as their contractions need whole operands:
#   * MLA factors (the absorbed attend contracts kv_lora_rank);
#   * all mamba parameters (x_proj/out_proj contract d_inner);
# MoE expert banks split the experts (expert parallelism).  Norms and
# biases replicate.
SERVE_PARAM_RULES: Tuple[Tuple[str, Tuple[Optional[str], ...]], ...] = (
    (r"(?:^|/)wq/w$", (None, "heads")),
    (r"(?:^|/)(?:wk|wv)/w$", (None, "kv_heads")),
    (r"(?:^|/)wo/w$", ("heads", None)),
    (r"(?:^|/)(?:w_up|w_gate)/w$", (None, "ff")),
    (r"(?:^|/)w_down/w$", ("ff", None)),
    (r"(?:^|/)(?:w_gate|w_up)$", ("experts", None, "ff")),
    (r"(?:^|/)w_down$", ("experts", None, None)),
    (r"(?:^|/)embed/w$", ("vocab", None)),
    (r"(?:^|/)head/w$", (None, "vocab")),
)
_SERVE_PARAM_RULES = tuple(
    (re.compile(pat), base) for pat, base in SERVE_PARAM_RULES
)


def serve_logical_for_path(path: str, ndim: int) -> Tuple[Optional[str], ...]:
    """Serving logical axes for a parameter path (rank + 1 leaves are
    scan-stacked over layer groups, as in :func:`logical_for_path`)."""
    for pat, base in _SERVE_PARAM_RULES:
        if pat.search(path):
            if ndim == len(base):
                return tuple(base)
            if ndim == len(base) + 1:
                return (None,) + tuple(base)
            break
    return (None,) * ndim


def serve_param_sharding_tree(shape_tree, mesh: Mesh):
    """``NamedSharding`` per parameter for serving on ``mesh``."""
    ctx = serving_context(mesh)

    def one(path, leaf):
        logical = serve_logical_for_path(_path_str(path), len(leaf.shape))
        return ctx.sharding(logical, leaf.shape)

    return jax.tree_util.tree_map_with_path(one, shape_tree)


# Paged-pool leaves by key.  GQA K/V pages are (groups, n_pages, page,
# n_kv, hd) — sharded over kv heads, as the attention that reads them.
# MLA latent pages contract kv_lora_rank in the absorbed attend and SSM
# states feed elementwise recurrences whose surrounding projections
# contract d_inner: both replicate.
_SERVE_POOL_LOGICAL: Dict[str, Tuple[Optional[str], ...]] = {
    "k": (None, None, None, "kv_heads", None),
    "v": (None, None, None, "kv_heads", None),
}


def _pool_logical(path, ndim: int) -> Tuple[Optional[str], ...]:
    key = ""
    for p in reversed(path):
        if hasattr(p, "key"):
            key = str(p.key)
            break
    logical = _SERVE_POOL_LOGICAL.get(key, (None,) * ndim)
    if len(logical) != ndim:
        logical = (None,) * ndim
    return logical


def serve_pool_sharding_tree(shape_tree, mesh: Mesh):
    """``NamedSharding`` per paged-pool leaf for serving."""
    ctx = serving_context(mesh)

    def one(path, leaf):
        return ctx.sharding(_pool_logical(path, len(leaf.shape)), leaf.shape)

    return jax.tree_util.tree_map_with_path(one, shape_tree)


def constrain_pool(pool):
    """Pin a cache pool RETURNED by a jitted serve program to the same
    layout :func:`serve_pool_sharding_tree` committed its input to.

    Without this the partitioner is free to hand the (donated) pool back
    in whatever layout it liked best internally; the session rebinds the
    result as the next call's input, whose sharding then differs from
    the traced one — a recompile per step, and a different layout again
    the step after.  No-op outside a serving context."""
    ctx = current()
    if ctx is None or not ctx.serving:
        return pool

    def one(path, leaf):
        return jax.lax.with_sharding_constraint(
            leaf, ctx.sharding(_pool_logical(path, leaf.ndim), leaf.shape)
        )

    return jax.tree_util.tree_map_with_path(one, pool)
