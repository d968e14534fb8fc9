"""Fully-jitted NSGA-II (Deb et al. 2002) over the DCIM design space.

This is the paper's "MOGA-based design space explorer" core: 4 objectives
[A, D, E, -T], constrained domination for the storage-equality-derived
box violation, binary tournament selection, uniform crossover and
step/reset mutation on the integer log2 genome, (mu + lambda) elitist
survival.  The entire generations loop is a single ``lax.scan``
inside one ``jax.jit`` — a full DSE run takes milliseconds, vs. the
paper's 30-minute budget per (precision, W_store) point.

Scenario parameters (bit-widths, bounds) are *traced data* — a
:class:`repro.core.scenario.ScenarioTable` row — so the whole algorithm
is ``vmap``-able over a leading scenario axis: :func:`run_batched`
evolves S scenarios' populations in ONE jitted program (one trace, S x P
individuals).  :func:`run_static` keeps the historical one-jit-per-space
path as the equivalence/benchmark reference, and :func:`run_unjitted`
the paper-faithful eager baseline.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import List

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import scenario as scen_mod
from .pareto import crowding_distance, non_dominated_sort, pareto_front_mask
from .scenario import N_GENES, ScenarioTable, as_row


@dataclasses.dataclass(frozen=True)
class NSGA2Config:
    pop_size: int = 128
    generations: int = 64
    p_crossover: float = 0.9
    p_mutate: float = 0.3
    p_step_mutate: float = 0.5   # fraction of mutations that are +/-1 steps
    seed: int = 0
    # Dominance matrix via the pareto_rank Pallas kernel: compiled on TPU,
    # interpreter-lowered to XLA on CPU (bit-exact either way, and tested
    # against the jnp path).  Set False to force the pure-jnp dominance.
    use_pallas: bool = True


@dataclasses.dataclass
class NSGA2Result:
    genes: np.ndarray        # (P, 3) final population
    objectives: np.ndarray   # (P, 4)
    violation: np.ndarray    # (P,)
    ranks: np.ndarray        # (P,)
    front_genes: np.ndarray  # (F, 3) deduped feasible rank-0 set
    front_objectives: np.ndarray  # (F, 4)


def _rank_and_crowd(F, v, use_pallas: bool):
    dom = None
    if use_pallas:
        from repro.kernels import ops as kops  # lazy: core stays standalone

        dom = kops.dominance_matrix(F, v)
    ranks = non_dominated_sort(F, v, dom=dom)
    crowd = crowding_distance(F, ranks)
    return ranks, crowd


def _tournament(key, ranks, crowd, n):
    P = ranks.shape[0]
    ka, kb = jax.random.split(key)
    i = jax.random.randint(ka, (n,), 0, P)
    j = jax.random.randint(kb, (n,), 0, P)
    better_i = (ranks[i] < ranks[j]) | (
        (ranks[i] == ranks[j]) & (crowd[i] > crowd[j])
    )
    return jnp.where(better_i, i, j)


def _make_children(key, pop, ranks, crowd, cfg: NSGA2Config, lo, hi):
    P = pop.shape[0]
    ksa, ksb, kxp, kxm, kmm, kms, kmr, kmp = jax.random.split(key, 8)
    pa = pop[_tournament(ksa, ranks, crowd, P)]
    pb = pop[_tournament(ksb, ranks, crowd, P)]

    do_x = jax.random.bernoulli(kxp, cfg.p_crossover, (P, 1))
    xmask = jax.random.bernoulli(kxm, 0.5, (P, N_GENES))
    child = jnp.where(do_x & xmask, pb, pa)

    mmask = jax.random.bernoulli(kmm, cfg.p_mutate, (P, N_GENES))
    step = jax.random.randint(kms, (P, N_GENES), 0, 2) * 2 - 1
    reset = jax.random.randint(kmr, (P, N_GENES), lo[None, :], hi[None, :] + 1)
    use_step = jax.random.bernoulli(kmp, cfg.p_step_mutate, (P, N_GENES))
    mutated = jnp.where(use_step, child + step, reset)
    child = jnp.where(mmask, mutated, child)
    return jnp.clip(child, lo[None, :], hi[None, :]).astype(jnp.int32)


def _survivors(F, v, comb, P, use_pallas):
    ranks, crowd = _rank_and_crowd(F, v, use_pallas)
    crowd_c = jnp.where(jnp.isinf(crowd), 1e30, crowd)
    order = jnp.lexsort((-crowd_c, ranks))
    return comb[order[:P]]


def make_step(space_or_row, cfg: NSGA2Config):
    """One NSGA-II generation as a ``lax.scan`` body.

    ``space_or_row`` may be a ``DesignSpace`` (bounds become trace
    constants, the historical behavior) or a ``ScenarioTable`` row of
    tracers (the batched path, ``vmap``-ed over scenarios)."""
    row = as_row(space_or_row)
    lo = jnp.asarray(row.gene_lo)
    hi = jnp.asarray(row.gene_hi)

    def step(carry, gen):
        pop, key = carry
        key, kc = jax.random.split(jax.random.fold_in(key, gen))
        F, v = scen_mod.evaluate(row, pop)
        ranks, crowd = _rank_and_crowd(F, v, cfg.use_pallas)
        children = _make_children(kc, pop, ranks, crowd, cfg, lo, hi)
        comb = jnp.concatenate([pop, children], axis=0)
        Fc, vc = scen_mod.evaluate(row, comb)
        pop = _survivors(Fc, vc, comb, cfg.pop_size, cfg.use_pallas)
        # Children are emitted for the elitist archive: the returned front
        # is extracted from *every candidate ever evaluated*, so a design
        # visited at gen 3 and later crowded out is never lost.
        return (pop, key), children

    return step


def init_population(space_or_row, cfg: NSGA2Config, key) -> jnp.ndarray:
    row = as_row(space_or_row)
    lo = jnp.asarray(row.gene_lo)
    hi = jnp.asarray(row.gene_hi)
    return jax.random.randint(
        key, (cfg.pop_size, N_GENES), lo[None, :], hi[None, :] + 1, jnp.int32
    )


def _evolve(row, cfg: NSGA2Config, key):
    """Init + generations scan for one scenario row.

    Shared by the batched (vmap-ed) and static (per-space jit) runners so
    both execute the identical program modulo whether scenario params are
    tracers or constants.  Final population ranking happens eagerly on
    the host (:func:`_final_ranks`): it would otherwise lower a second
    copy of the rank/crowd graph outside the scan and roughly double the
    compile time of the batched program."""
    pop = init_population(row, cfg, key)
    step = make_step(row, cfg)
    (pop, _), visited = lax.scan(step, (pop, key), jnp.arange(cfg.generations))
    F, v = scen_mod.evaluate(row, pop)
    archive = jnp.concatenate([visited.reshape(-1, N_GENES), pop], axis=0)
    return pop, F, v, archive


@partial(jax.jit, static_argnums=(2,))
def _ranks_jit(F, v, use_pallas: bool):
    dom = None
    if use_pallas:
        from repro.kernels import ops as kops

        dom = kops.dominance_matrix(F, v)
    return non_dominated_sort(F, v, dom=dom)


def _final_ranks(F, v, cfg: NSGA2Config) -> np.ndarray:
    with jax.profiler.TraceAnnotation("repro.nsga2.final_ranks"):
        return np.asarray(_ranks_jit(jnp.asarray(F), jnp.asarray(v), cfg.use_pallas))


# Packed keys are deduplicated through a presence table (one boolean per
# possible key) while there are at most this many possible keys per row,
# and by sorting the keys above that.
_TABLE_KEYS_PER_ROW = 4


def _packed_unique(archives: List[np.ndarray]):
    """Every archive's distinct rows through ONE 1-D dedup, or None where
    the rows cannot be packed into an int64 key.

    The key is mixed-radix: the archive's index is the leading digit, then
    each gene column offset by its minimum over all archives, in a radix of
    that column's range.  Ascending keys are therefore the archives in turn,
    each in lexicographic row order: ``np.unique(a, axis=0)``'s order."""
    flat = np.concatenate(archives)
    if not len(flat) or not np.can_cast(flat.dtype, np.int64):
        return None
    # One contiguous int64 row per gene: numpy reduces and scales those
    # several times faster than the columns of the (N, 3) array.
    genes = np.ascontiguousarray(flat.T, dtype=np.int64)
    lo = [int(x) for x in genes.min(axis=1)]
    radix = [int(h) - l + 1 for h, l in zip(genes.max(axis=1), lo)]
    strides = [math.prod(radix[c + 1:]) for c in range(N_GENES)]
    per_archive = strides[0] * radix[0]
    n_keys = len(archives) * per_archive
    if n_keys >= 2**63:
        return None
    key = np.repeat(np.arange(len(archives), dtype=np.int64) * per_archive,
                    [len(a) for a in archives])
    for c in range(N_GENES):
        key += (genes[c] - lo[c]) * strides[c]
    if n_keys <= _TABLE_KEYS_PER_ROW * len(flat):
        seen = np.zeros(n_keys, dtype=bool)
        seen[key] = True
        key = np.flatnonzero(seen)
    else:
        key = np.unique(key)
    bounds = np.searchsorted(
        key, np.arange(len(archives) + 1, dtype=np.int64) * per_archive)
    rows = np.empty((len(key), N_GENES), dtype=flat.dtype)
    rest = key % per_archive
    for c in range(N_GENES):
        digit, rest = np.divmod(rest, strides[c])
        rows[:, c] = digit + lo[c]
    return [rows[b:e] for b, e in zip(bounds[:-1], bounds[1:])]


def _dedup(archives) -> List[np.ndarray]:
    """Each archive's distinct genomes, sorted (``np.unique`` by row).

    All archives are deduplicated at once through a packed integer key
    (:func:`_packed_unique`); rows too wide for it fall back to
    ``np.unique(a, axis=0)`` per archive, with the same result.  The span
    records ``packed``, the rows that went through the key (0 on the
    fallback)."""
    archives = [np.asarray(a).reshape(-1, N_GENES) for a in archives]
    rows_in = sum(len(a) for a in archives)
    with jax.profiler.TraceAnnotation(
        "repro.nsga2.dedup", rows_in=rows_in
    ) as span:
        arches, packed = _packed_unique(archives), rows_in
        if arches is None:
            arches, packed = [np.unique(a, axis=0) for a in archives], 0
        span.set_metadata(rows_out=sum(len(a) for a in arches), packed=packed)
    return arches


@jax.jit
def _archive_front_jit(row, genes):
    """Evaluate a (bucket-padded) archive and mask its feasible Pareto
    front in one compiled program.  Padding rows are copies of row 0, so
    they change no real entry's domination status."""
    F, v = scen_mod.evaluate(row, genes)
    mask = pareto_front_mask(F, v) & (v <= 0.0)
    return F, v, mask


@partial(jax.jit, static_argnums=(1,))
def _run_batched_jit(table: ScenarioTable, cfg: NSGA2Config, keys):
    return jax.vmap(lambda row, key: _evolve(row, cfg, key))(table, keys)


@partial(jax.jit, static_argnums=(0, 1))
def _run_static_jit(space, cfg: NSGA2Config, key):
    return _evolve(space.scenario, cfg, key)


def _extract_result(
    row, pop, F, v, ranks, archive, bucket=None, deduped=False
) -> NSGA2Result:
    """Host-side front extraction from the elitist archive.

    Dedup on host, then re-evaluate the archive through the shared
    bucketed front program (``_archive_front_jit``) — the same program
    the brute-force oracle uses — instead of trusting in-loop values:
    in-loop float32 fusion can differ by 1 ULP, which would make
    objectives inconsistent with external (oracle) evaluation.

    ``bucket`` optionally pins the padded archive shape so several
    scenarios share one compile, and ``deduped=True`` skips the
    dedup for archives the caller already deduplicated (see
    :func:`run_batched`)."""
    if deduped:
        arch = np.asarray(archive).reshape(-1, N_GENES)
    else:
        (arch,) = _dedup([archive])
    with jax.profiler.TraceAnnotation("repro.nsga2.archive_front"):
        arch_p, n = scen_mod.pad_to_bucket(arch, bucket)
        # row() slices back to numpy scalars — transfer explicitly.
        # device_put (not jnp.asarray): converting a host scalar's dtype
        # routes through convert_element_type, an *implicit* transfer that
        # jax.transfer_guard("disallow") rejects.
        row = jax.tree.map(jax.device_put, row)
        aF, av, mask = jax.tree.map(
            lambda a: np.asarray(a)[:n],
            _archive_front_jit(row, jnp.asarray(arch_p)),
        )
        return NSGA2Result(
            genes=np.asarray(pop),
            objectives=np.asarray(F),
            violation=np.asarray(v),
            ranks=np.asarray(ranks),
            front_genes=arch[mask],
            front_objectives=np.asarray(aF)[mask],
        )


def _seed_key(seed: int):
    """PRNGKey whose seed transfer is *explicit* (``device_put``).

    ``jax.random.PRNGKey(int)`` moves the seed scalar host->device
    implicitly, which trips ``jax.transfer_guard("disallow")`` — the
    transfers lint replays :func:`run_batched` under that guard."""
    return jax.random.PRNGKey(jax.device_put(np.int64(seed)))


def run_batched(
    table: ScenarioTable, cfg: NSGA2Config = NSGA2Config()
) -> List[NSGA2Result]:
    """Evolve ALL scenarios of ``table`` in one jitted, vmapped program.

    Each scenario uses the same RNG stream as a standalone
    :func:`run`/:func:`run_static` call with the same config, so the
    batched fronts match the sequential per-scenario path exactly.

    Under ``jax.profiler`` the call records nested ``repro.nsga2.*``
    spans (``docs/dse.md``, "Tracing a DSE call"); the outer one carries
    ``programs``, the jitted programs the call launched."""
    S = len(table)
    with jax.profiler.TraceAnnotation(
        "repro.nsga2.run_batched",
        scenarios=S, pop=cfg.pop_size, generations=cfg.generations,
    ) as call:
        seed = np.int64(cfg.seed)
        with jax.profiler.TraceAnnotation(
            "repro.nsga2.upload",
            bytes=seed.nbytes + sum(a.nbytes for a in jax.tree.leaves(table)),
        ):
            key = _seed_key(seed)
            keys = jnp.broadcast_to(key, (S,) + key.shape)
            programs = 2  # the seed's threefry and the broadcast, each eager
            # Tables are built with numpy leaves; transfer them explicitly so
            # the jit call itself stays clean under jax.transfer_guard (the
            # transfers lint replays this path under "disallow").
            table = jax.tree.map(jax.device_put, table)
        with jax.profiler.TraceAnnotation("repro.nsga2.dispatch"):
            out = _run_batched_jit(table, cfg, keys)
            programs += 1
        # Extraction below is host-side (dedup, per-scenario slicing):
        # pull the batch to host ONCE.  Indexing the device arrays per
        # scenario instead would implicitly transfer each index scalar —
        # the transfers lint runs this path under a disallow guard.
        with jax.profiler.TraceAnnotation(
            "repro.nsga2.wait", bytes=sum(x.nbytes for x in out)
        ):
            pops, F, v, archives = (np.asarray(x) for x in out)
        # Dedup every scenario's archive first, then extract all fronts
        # through ONE padded shape: S scenarios share a single
        # ``_archive_front_jit`` compile instead of one per distinct size.
        arches = _dedup(archives)
        with jax.profiler.TraceAnnotation("repro.nsga2.fronts"):
            bucket = scen_mod._bucket(max(a.shape[0] for a in arches))
            results = []
            for i in range(S):
                ranks = _final_ranks(F[i], v[i], cfg)
                results.append(_extract_result(
                    table.row(i), pops[i], F[i], v[i], ranks, arches[i],
                    bucket=bucket, deduped=True,
                ))
                programs += 2  # _ranks_jit and _archive_front_jit
        call.set_metadata(programs=programs)
    return results


def run(space, cfg: NSGA2Config = NSGA2Config()) -> NSGA2Result:
    """Run NSGA-II for one scenario through the batched pipeline (S=1).

    The returned front is the non-dominated subset of the *elitist
    archive* (every candidate ever evaluated), deduplicated — a design
    visited early and later crowded out is never lost."""
    return run_batched(space.to_table(), cfg)[0]


def run_static(space, cfg: NSGA2Config = NSGA2Config()) -> NSGA2Result:
    """Historical per-scenario path: ``space`` is a *static* jit argument,
    so every distinct (precision, W_store) re-traces and re-compiles.

    Kept as the sequential reference that :func:`run_batched` is tested
    against (bit-identical fronts) and benchmarked against
    (``benchmarks/bench_dse.py``)."""
    key = jax.random.PRNGKey(cfg.seed)
    pop, F, v, archive = _run_static_jit(space, cfg, key)
    return _extract_result(
        space.scenario, pop, F, v, _final_ranks(F, v, cfg), archive
    )


def run_unjitted(space, cfg: NSGA2Config = NSGA2Config()) -> NSGA2Result:
    """Paper-faithful baseline: eager per-generation dispatch (no jit of
    the generations loop).  Identical operators and results modulo RNG
    stream; exists so EXPERIMENTS.md §Perf-DSE can quantify the win of
    compiling the whole DSE into one XLA program."""
    row = space.scenario
    key = jax.random.PRNGKey(cfg.seed)
    lo = jnp.asarray(row.gene_lo)
    hi = jnp.asarray(row.gene_hi)
    pop = init_population(row, cfg, key)
    visited = [np.asarray(pop)]
    for gen in range(cfg.generations):
        key, kc = jax.random.split(jax.random.fold_in(key, gen))
        F, v = scen_mod.evaluate(row, pop)
        ranks, crowd = _rank_and_crowd(F, v, cfg.use_pallas)
        children = _make_children(kc, pop, ranks, crowd, cfg, lo, hi)
        comb = jnp.concatenate([pop, children], axis=0)
        Fc, vc = scen_mod.evaluate(row, comb)
        pop = _survivors(Fc, vc, comb, cfg.pop_size, cfg.use_pallas)
        pop.block_until_ready()
        visited.append(np.asarray(children))
    F, v = scen_mod.evaluate(row, pop)
    ranks, _ = _rank_and_crowd(F, v, cfg.use_pallas)
    archive = np.concatenate(visited + [np.asarray(pop)])
    return _extract_result(row, pop, F, v, ranks, archive)


# ------------------------------ lint contract --------------------------------
from repro.analysis.registry import Built, Replay, register_contract  # noqa: E402


@register_contract(
    "nsga2.run_batched",
    checks=("recompile", "transfers", "precision"),
    description="batched DSE at a tiny budget: two scenario tables with "
                "equal shapes but different contents must share ONE "
                "compiled program (scenario params are traced data), "
                "the host pipeline must transfer only explicitly, and "
                "the traced evolve program must hold f32 discipline "
                "(no f64 from python-float scenario params)",
)
def _build_nsga2_contract() -> Built:
    from repro.analysis.jaxpr_tools import canonical_signature
    from repro.analysis.registry import PrecisionPolicy

    cfg = NSGA2Config(pop_size=16, generations=4)
    t1 = ScenarioTable.from_specs([("int8", 16384), ("int4", 16384)])
    # Same static metadata as t1 (all-INT => any_fp/all_fp agree), so a
    # single compiled program must serve both tables.
    t2 = ScenarioTable.from_specs([("int16", 32768), ("int2", 16384)])

    base = int(_run_batched_jit._cache_size())
    signatures = []
    key = jax.random.PRNGKey(cfg.seed)
    for t in (t1, t2):
        keys = jnp.broadcast_to(key, (len(t),) + key.shape)
        signatures.append((
            "run_batched",
            canonical_signature((jax.tree.map(jnp.asarray, t), keys)),
        ))
        run_batched(t, cfg)
    grown = int(_run_batched_jit._cache_size()) - base
    replay = Replay(
        signatures=signatures,
        max_programs={"run_batched": 1},
        live_counts={"run_batched": grown},
        live_budget={"run_batched": 1},
    )

    def hot():
        return run_batched(t1, cfg)

    keys1 = jnp.broadcast_to(key, (len(t1),) + key.shape)
    evolve_jaxpr = jax.make_jaxpr(
        lambda t, k: _run_batched_jit(t, cfg, k)
    )(jax.tree.map(jnp.asarray, t1), keys1)
    return Built(
        hot=hot, hot_label="run_batched pipeline", replay=replay,
        hot_jaxprs=[("run_batched", evolve_jaxpr)],
        precision=PrecisionPolicy(compute_dtype="float32"),
    )
