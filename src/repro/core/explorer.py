"""MOGA-based design space explorer (paper Fig. 4, §III-B).

Drives NSGA-II across (precision, W_store, template) scenarios, merges
fronts across templates/precisions into one candidate set (re-extracting
the joint Pareto front, as the paper's "Pareto set containing both
integer and floating-point solutions"), applies *user-defined
distillation* (application constraints), and hands selected points to
the template-based generator.

Since the scenario-table refactor, ``explore_multi`` is *batched by
default*: scenario parameters are traced data
(:class:`repro.core.scenario.ScenarioTable`), so all S scenarios evolve
in ONE jitted program (one trace, S x P populations) instead of
re-tracing NSGA-II per scenario.  The sequential per-scenario loop is
kept (``batched=False``) as the equivalence/benchmark reference.

Also provides the exhaustive brute-force oracle (the log2-linear storage
constraint makes the space finitely enumerable) and distributed
*island-model* NSGA-II over a JAX mesh: :func:`run_islands` (one
scenario, islands along one axis) and :func:`run_islands_multi`
(scenario x island sharding on a 2-D mesh via ``repro.dist`` logical
axes; ring migration via ``lax.ppermute`` stays per-scenario).
"""
from __future__ import annotations

import dataclasses
import math
import time
from functools import partial
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from . import nsga2
from . import scenario as scen_mod
from .cells import CALIBRATED, CellLibrary, TechParams, TSMC28
from .macros import physical
from .pareto import pareto_front_mask
from .precision import Precision, get as get_precision
from .scenario import N_GENES, ScenarioTable
from .space import DesignSpace


@dataclasses.dataclass
class ParetoPoint:
    """One explored design, fully described for reports and codegen."""

    precision: str
    w_store: int
    N: int
    H: int
    L: int
    k: int
    genes: np.ndarray
    # normalized costs
    area: float
    delay: float
    energy: float
    throughput: float
    # physical metrics (calibrated tech, activity applied)
    area_mm2: float
    delay_ns: float
    energy_nJ: float
    tops: float
    tops_per_w: float
    tops_per_mm2: float

    @property
    def objectives(self) -> np.ndarray:
        return np.array(
            [self.area, self.delay, self.energy, -self.throughput], np.float32
        )

    def summary(self) -> str:
        return (
            f"{self.precision:>5} W={self.w_store:>6} N={self.N:<5} H={self.H:<5}"
            f" L={self.L:<3} k={self.k:<2} | {self.area_mm2:8.4f} mm^2"
            f" {self.delay_ns:6.2f} ns {self.energy_nJ:8.4f} nJ"
            f" {self.tops:7.3f} TOPS {self.tops_per_w:8.2f} TOPS/W"
        )


@partial(jax.jit, static_argnums=(2, 3))
def _point_metrics_jit(row, genes, tech: TechParams, activity: float):
    c = scen_mod.costs(row, genes)
    return c, physical(c, tech, activity), scen_mod.decode(row, genes)


def _points_from_genes(
    space: DesignSpace,
    genes: np.ndarray,
    tech: TechParams,
    activity: float,
    bucket: Optional[int] = None,
) -> List[ParetoPoint]:
    if genes.size == 0:
        return []
    gp, n = scen_mod.pad_to_bucket(genes.reshape(-1, N_GENES), bucket)
    costs, phys, nhlk = jax.tree.map(
        lambda a: np.asarray(a)[:n],
        _point_metrics_jit(space.scenario, jnp.asarray(gp), tech, activity),
    )
    N, H, L, k = nhlk
    out = []
    for i in range(genes.shape[0]):
        out.append(
            ParetoPoint(
                precision=space.prec.name,
                w_store=space.w_store,
                N=int(N[i]),
                H=int(H[i]),
                L=int(L[i]),
                k=int(k[i]),
                genes=np.asarray(genes[i]),
                area=float(costs.area[i]),
                delay=float(costs.delay[i]),
                energy=float(costs.energy[i]),
                throughput=float(costs.throughput[i]),
                area_mm2=float(phys.area_mm2[i]),
                delay_ns=float(phys.delay_ns[i]),
                energy_nJ=float(phys.energy_nJ[i]),
                tops=float(phys.tops[i]),
                tops_per_w=float(phys.tops_per_w[i]),
                tops_per_mm2=float(phys.tops_per_mm2[i]),
            )
        )
    return out


def _normalize_scenarios(scenarios: Sequence[tuple]) -> List[tuple]:
    out = []
    for prec, w in scenarios:
        out.append((get_precision(prec) if isinstance(prec, str) else prec, w))
    return out


def brute_force_front(space: DesignSpace) -> np.ndarray:
    """Exact Pareto-optimal genomes by full enumeration (the oracle).

    Routed through the same jitted evaluate+front program as the NSGA-II
    archive extraction (``enumerate_feasible`` only yields
    zero-violation genomes, so the feasibility mask is a no-op here)."""
    genes = space.enumerate_feasible()
    gp, n = scen_mod.pad_to_bucket(genes)
    _, _, mask = nsga2._archive_front_jit(space.scenario, jnp.asarray(gp))
    return np.asarray(genes)[np.asarray(mask)[:n]]


def explore(
    precision: str | Precision,
    w_store: int,
    cfg: nsga2.NSGA2Config = nsga2.NSGA2Config(),
    lib: CellLibrary = TSMC28,
    tech: TechParams = CALIBRATED,
    activity: float = 1.0,
    method: str = "nsga2",
    include_selection_mux: bool = False,
) -> List[ParetoPoint]:
    """Explore one (precision, W_store) scenario; returns its Pareto set.

    ``method``: ``"nsga2"`` (batched pipeline, scenario params as traced
    data), ``"nsga2-static"`` (historical one-jit-per-space reference),
    or ``"brute"`` (exhaustive oracle).
    """
    prec = get_precision(precision) if isinstance(precision, str) else precision
    space = DesignSpace(
        prec=prec, w_store=w_store, lib=lib,
        include_selection_mux=include_selection_mux,
    )
    if method == "brute":
        fg = brute_force_front(space)
    elif method == "nsga2-static":
        fg = nsga2.run_static(space, cfg).front_genes
    elif method == "nsga2":
        fg = nsga2.run(space, cfg).front_genes
    else:
        raise ValueError(f"unknown method {method!r}")
    return _points_from_genes(space, fg, tech, activity)


def explore_multi(
    scenarios: Sequence[tuple],
    cfg: nsga2.NSGA2Config = nsga2.NSGA2Config(),
    cross_dominate: bool = False,
    batched: bool = True,
    lib: CellLibrary = TSMC28,
    tech: TechParams = CALIBRATED,
    activity: float = 1.0,
    method: str = "nsga2",
    include_selection_mux: bool = False,
    store=None,
    record_name: str = "explore_multi",
) -> List[ParetoPoint]:
    """Union of per-scenario fronts — the paper's merged INT+FP candidate
    set handed to user distillation.

    ``scenarios`` is a list of (precision, w_store).  With
    ``batched=True`` (default) all scenarios run in ONE jitted NSGA-II
    (``nsga2.run_batched`` over a :class:`ScenarioTable`); with
    ``batched=False`` the historical sequential loop runs one jit per
    scenario — both produce identical fronts (tested).

    By default points of different precisions do NOT dominate each other
    (an INT8 design is not a functional substitute for a BF16 one; the
    paper's distillation step picks by application).
    ``cross_dominate=True`` re-reduces the union to a single joint front
    instead.

    ``store`` may be a :class:`repro.core.results.ResultStore`; the
    merged front and wall-time are then persisted under ``record_name``.
    """
    t0 = time.perf_counter()
    specs = _normalize_scenarios(scenarios)
    pts: List[ParetoPoint] = []
    if batched and method == "nsga2" and specs:
        table = ScenarioTable.from_specs(
            specs, lib=lib, include_selection_mux=include_selection_mux
        )
        results = nsga2.run_batched(table, cfg)
        # One padded shape for every scenario's front -> one
        # _point_metrics_jit compile for the whole batch.
        sizes = [r.front_genes.shape[0] for r in results if r.front_genes.size]
        bucket = scen_mod._bucket(max(sizes)) if sizes else None
        for (prec, w), res in zip(specs, results):
            space = DesignSpace(
                prec=prec, w_store=w, lib=lib,
                include_selection_mux=include_selection_mux,
            )
            pts.extend(
                _points_from_genes(
                    space, res.front_genes, tech, activity, bucket=bucket
                )
            )
    else:
        # Sequential reference: one (re-)jit per scenario.
        seq_method = "nsga2-static" if method == "nsga2" else method
        for prec, w in specs:
            pts.extend(
                explore(
                    prec, w, cfg, lib=lib, tech=tech, activity=activity,
                    method=seq_method,
                    include_selection_mux=include_selection_mux,
                )
            )
    if pts and cross_dominate:
        F = jnp.asarray(np.stack([p.objectives for p in pts]))
        mask = np.asarray(pareto_front_mask(F))
        pts = [p for p, m in zip(pts, mask) if m]
    if store is not None:
        from .results import front_payload

        payload = front_payload(pts)
        payload["scenarios"] = [(p.name, w) for p, w in specs]
        payload["batched"] = batched
        payload["cross_dominate"] = cross_dominate
        store.put(record_name, payload, kind="dse",
                  wall_s=time.perf_counter() - t0)
    return pts


def distill(
    points: Sequence[ParetoPoint],
    max_area_mm2: Optional[float] = None,
    max_power_mW: Optional[float] = None,
    max_delay_ns: Optional[float] = None,
    min_tops: Optional[float] = None,
    min_tops_per_w: Optional[float] = None,
    top: Optional[int] = None,
    sort_by: str = "edp",
) -> List[ParetoPoint]:
    """User-defined distillation (paper Fig. 4): filter the Pareto set by
    application constraints, then rank by a scalar figure of merit."""
    sel = []
    for p in points:
        power_mW = p.energy_nJ / max(p.delay_ns, 1e-12) * 1e3
        if max_area_mm2 is not None and p.area_mm2 > max_area_mm2:
            continue
        if max_power_mW is not None and power_mW > max_power_mW:
            continue
        if max_delay_ns is not None and p.delay_ns > max_delay_ns:
            continue
        if min_tops is not None and p.tops < min_tops:
            continue
        if min_tops_per_w is not None and p.tops_per_w < min_tops_per_w:
            continue
        sel.append(p)
    keyfns = {
        "edp": lambda p: p.energy_nJ * p.delay_ns,
        "area": lambda p: p.area_mm2,
        "delay": lambda p: p.delay_ns,
        "energy": lambda p: p.energy_nJ,
        "tops": lambda p: -p.tops,
        "tops_per_w": lambda p: -p.tops_per_w,
    }
    sel.sort(key=keyfns[sort_by])
    return sel[:top] if top else sel


# --------------------------------------------------------------------------
# Island-model NSGA-II: population-parallel DSE over a device mesh.
# --------------------------------------------------------------------------
def _island_body(row, cfg, n_isl, axis, rounds, gens_per_round, n_migrants):
    """Per-island evolution for one scenario: ``pop (P, 3), key -> (pop,
    archive)``.  Every round the best ``n_migrants`` individuals migrate
    along a ring over mesh axis ``axis`` (``lax.ppermute``) and replace
    the worst.  Shared by the single-scenario and scenario x island
    runners."""
    step = nsga2.make_step(row, cfg)

    def body(pop, key):
        def one_round(carry, r):
            pop, key = carry
            key = jax.random.fold_in(key, r)
            (pop, _), visited = lax.scan(
                step, (pop, key), jnp.arange(gens_per_round)
            )
            F, v = scen_mod.evaluate(row, pop)
            ranks, crowd = nsga2._rank_and_crowd(F, v, cfg.use_pallas)
            crowd_c = jnp.where(jnp.isinf(crowd), 1e30, crowd)
            order = jnp.lexsort((-crowd_c, ranks))
            best = pop[order[:n_migrants]]
            if n_isl > 1:
                perm = [(i, (i + 1) % n_isl) for i in range(n_isl)]
                incoming = lax.ppermute(best, axis, perm)
            else:
                incoming = best
            pop = pop.at[order[-n_migrants:]].set(incoming)
            return (pop, key), visited.reshape(-1, N_GENES)

        (pop, _), visited = lax.scan(one_round, (pop, key), jnp.arange(rounds))
        archive = jnp.concatenate([visited.reshape(-1, N_GENES), pop], axis=0)
        return pop, archive

    return body


def _islands_result(row, pops, archives) -> nsga2.NSGA2Result:
    """Pool one scenario's islands and extract the archive front."""
    pop = np.asarray(pops).reshape(-1, N_GENES)
    F, v = scen_mod.evaluate(row, jnp.asarray(pop))
    F, v = np.asarray(F), np.asarray(v)
    # 0 for the pooled population's front, 1 otherwise.
    ranks = (~np.asarray(
        pareto_front_mask(jnp.asarray(F), jnp.asarray(v))
    )).astype(np.int32)
    archive = np.concatenate(
        [np.asarray(archives).reshape(-1, N_GENES), pop], axis=0
    )
    return nsga2._extract_result(row, pop, F, v, ranks, archive)


def run_islands(
    space: DesignSpace,
    cfg: nsga2.NSGA2Config = nsga2.NSGA2Config(),
    mesh: Optional[Mesh] = None,
    axis: str = "data",
    rounds: int = 4,
    gens_per_round: int = 16,
    n_migrants: int = 8,
) -> nsga2.NSGA2Result:
    """NSGA-II islands, one per device along ``axis``; every round the
    best ``n_migrants`` individuals migrate along a ring
    (``lax.ppermute``) and replace the worst.  Scales the paper's DSE to
    pods with zero algorithmic drift (islands are plain NSGA-II).
    """
    if mesh is None:
        dev = np.array(jax.devices())
        mesh = Mesh(dev.reshape(-1), (axis,))
    n_isl = mesh.shape[axis]
    row = space.scenario
    island = _island_body(
        row, cfg, n_isl, axis, rounds, gens_per_round, n_migrants
    )

    def island_body(pop, key):
        # pop: (1, P, 3) local block -> squeeze island dim inside shard_map
        pop, archive = island(pop[0], key[0])
        return pop[None], archive[None]

    key = jax.random.PRNGKey(cfg.seed)
    keys = jax.random.split(key, n_isl)
    pops = jax.vmap(lambda k: nsga2.init_population(row, cfg, k))(keys)

    body = jax.shard_map(
        island_body,
        mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=(P(axis), P(axis)),
        check_vma=False,
    )
    pops, archives = jax.jit(body)(pops, keys)
    return _islands_result(row, pops, archives)


def run_islands_multi(
    scenarios: Sequence[tuple] | ScenarioTable,
    cfg: nsga2.NSGA2Config = nsga2.NSGA2Config(),
    mesh: Optional[Mesh] = None,
    rounds: int = 4,
    gens_per_round: int = 16,
    n_migrants: int = 8,
    scenario_axis: str = "scenario",
    island_axis: str = "island",
) -> List[nsga2.NSGA2Result]:
    """Scenario x island sharded DSE: S scenarios, each with one NSGA-II
    island per device along ``island_axis``, scenarios sharded (and
    locally vmapped) along ``scenario_axis``.

    The 2-D mesh layout is resolved through ``repro.dist`` logical axes
    (``MeshContext`` with ``{"scenario": scenario_axis, "island":
    island_axis}`` rules) so the same code runs from a 1-chip CPU box
    (everything local, ring degenerate) to a pod slice.  Ring migration
    (``lax.ppermute``) runs over ``island_axis`` only — migration never
    crosses scenarios, keeping each scenario plain island NSGA-II.
    """
    table = (
        scenarios
        if isinstance(scenarios, ScenarioTable)
        else ScenarioTable.from_specs(_normalize_scenarios(scenarios))
    )
    S = len(table)
    if mesh is None:
        dev = np.array(jax.devices())
        s_mesh = math.gcd(S, dev.size)
        mesh = Mesh(
            dev.reshape(s_mesh, dev.size // s_mesh),
            (scenario_axis, island_axis),
        )
    from repro.dist.sharding import MeshContext

    ctx = MeshContext(
        mesh,
        rules={"scenario": (scenario_axis,), "island": (island_axis,)},
    )
    n_isl = mesh.shape[island_axis]
    if S % mesh.shape[scenario_axis]:
        raise ValueError(
            f"{S} scenarios not divisible by scenario mesh axis "
            f"{mesh.shape[scenario_axis]}"
        )

    key = jax.random.PRNGKey(cfg.seed)
    keys = jax.random.split(key, n_isl)                     # (I, 2)
    keys = jnp.broadcast_to(keys, (S,) + keys.shape)        # (S, I, 2)
    # Per-scenario gene boxes: vmap the init over scenarios x islands.
    pops = jax.vmap(
        lambda row, k: jax.vmap(
            lambda kk: nsga2.init_population(row, cfg, kk)
        )(k)
    )(table, keys)

    def shard_body(tbl, pops, keys):
        # tbl leaves: (S_loc, ...); pops/keys: (S_loc, 1, ...) — one
        # island per device along island_axis, local scenarios vmapped.
        def one_scenario(row, pop, key):
            island = _island_body(
                row, cfg, n_isl, island_axis, rounds, gens_per_round,
                n_migrants,
            )
            pop, archive = island(pop[0], key[0])
            return pop[None], archive[None]

        return jax.vmap(one_scenario)(tbl, pops, keys)

    # Logical layout via repro.dist: scenarios on scenario_axis, islands
    # on island_axis; the table's per-scenario params shard with their
    # scenario block.
    scen_spec = ctx.spec(("scenario",), (S,))
    both_spec = ctx.spec(("scenario", "island"), (S, n_isl))
    body = jax.shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(scen_spec, both_spec, both_spec),
        out_specs=(both_spec, both_spec),
        check_vma=False,
    )
    pops, archives = jax.jit(body)(table, pops, keys)
    return [
        _islands_result(table.row(i), pops[i], archives[i]) for i in range(S)
    ]


def timed_explore(precision: str, w_store: int, cfg=None) -> dict:
    """DSE wall-time probe for the paper's '30 minutes per scenario' claim."""
    cfg = cfg or nsga2.NSGA2Config()
    t0 = time.perf_counter()
    pts = explore(precision, w_store, cfg)
    t1 = time.perf_counter()
    return dict(
        precision=precision,
        w_store=w_store,
        seconds=t1 - t0,
        front_size=len(pts),
    )
