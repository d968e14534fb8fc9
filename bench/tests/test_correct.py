"""``correct`` at a size the CPU holds: a sound run passes, and a run
whose timed path is broken underneath fails.  The harness's look for a
chip is skipped: the cell runners are called directly."""
import dataclasses
import json
import pathlib

import jax
import numpy as np
import pytest

from bench import dse, dse_reference, harness, reference, serve
from repro.core import nsga2
from repro.core.explorer import _normalize_scenarios
from repro.core.scenario import ScenarioTable

DATA = pathlib.Path(__file__).resolve().parent / "data"
BENCH = {"per_layer": [], "end_to_end": [
    {"name": n} for n in ("ttft_p95_ms", "itl_p95_ms", "output_tokens_per_s", "setup_s")]}


def _load(name):
    return json.loads((DATA / f"{name}.json").read_text())


def _serve(seed=2 ** 31 + 11, seconds=3.0):
    return serve.run(BENCH, {"name": "smoke"}, _load("qwen-smoke"), _load("chat-smoke"),
                     seed, seconds, False, harness.device_info())


def _dse(seed=2 ** 31 + 5, seconds=1.0):
    return dse.run(BENCH, {"name": "smoke"}, _load("dse-smoke"), {}, seed, seconds,
                   False, harness.device_info())


def test_sound_serving_run_is_correct():
    result, compared = _serve()
    assert result["correct"], compared
    assert result["attempted"] > 10 and result["failed"] == 0
    assert set(result["metrics"]) == {"ttft_p95_ms", "itl_p95_ms",
                                      "output_tokens_per_s", "setup_s"}


def test_sound_docqa_run_is_correct(monkeypatch):
    """The session mix through the traced path: asks after a session's
    first find its document in the prefix cache, and the run is
    correct."""
    monkeypatch.setattr(harness, "peaks", lambda kind: {})
    bench = {**BENCH, "per_layer": [{"name": "prefix_hit_share", "unit": "%"}]}
    result, compared = serve.run(bench, {"name": "smoke"}, _load("qwen-smoke"),
                                 _load("docqa-smoke"), 2 ** 31 + 13, 6.0, True,
                                 harness.device_info())
    assert result["correct"], compared
    assert result["attempted"] > 10 and result["failed"] == 0
    assert result["metrics"]["prefix_hit_share"]["value"] > 0


def test_altered_token_fails(monkeypatch):
    """Every sampled token moved to its neighbour id where the serving
    programs produce it."""
    from repro.serve import scheduler

    orig = scheduler.sample_tokens
    monkeypatch.setattr(scheduler, "sample_tokens",
                        lambda logits, *a: (orig(logits, *a) + 1) % logits.shape[-1])
    result, compared = _serve()
    assert not result["correct"]
    assert compared["max_logit_gap"]["value"] > compared["max_logit_gap"]["limit"]


def test_control_fails_the_limit():
    """The reference at float8 in the program's place, at the smoke
    configuration: the gap of the token it puts first exceeds the limit
    the smoke configuration states."""
    conf = _load("qwen-smoke")
    c, seed = conf["config"], 7
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, c["vocab_size"], n).astype(np.int32) for n in (40, 90, 120)]
    served = [rng.integers(1, c["vocab_size"], 24).astype(np.int32) for _ in prompts]
    gaps = reference.served_gaps(c, seed, prompts, served, control=True)
    assert max(g.max() for g in gaps) > conf["limits"]["max_logit_gap"]


def test_sound_dse_run_is_correct():
    result, compared = _dse()
    assert result["correct"], compared
    assert result["attempted"] >= 1


@pytest.fixture
def fresh_programs():
    """Trace the DSE programs anew, so that a fault planted in the
    program reaches them, and drop those traces afterwards."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_altered_front_fails(monkeypatch):
    """One objective of every returned front point moved by 1%."""
    from repro.core import nsga2

    orig = nsga2._extract_result

    def altered(*a, **k):
        r = orig(*a, **k)
        r.front_objectives = r.front_objectives * np.float32(1.01)
        return r

    monkeypatch.setattr(nsga2, "_extract_result", altered)
    result, compared = _dse()
    assert not result["correct"]
    assert compared["max_rel_objective_error"]["value"] > 1e-3


def test_dropped_front_points_fail(monkeypatch):
    """Every other point of each returned front left out."""
    from repro.core import nsga2

    orig = nsga2._extract_result

    def halved(*a, **k):
        r = orig(*a, **k)
        return dataclasses.replace(r, front_genes=r.front_genes[::2],
                                   front_objectives=r.front_objectives[::2])

    monkeypatch.setattr(nsga2, "_extract_result", halved)
    result, compared = _dse()
    assert not result["correct"]
    c = compared["missing_front_points"]
    assert c["value"] > c["limit"]


def test_altered_dominance_kernel_fails(monkeypatch, fresh_programs):
    """The dominance matrix, where the kernel produces it, with equal
    points dominating each other (the strict part of Pareto dominance
    left out): a converged population holds duplicates, whose ranks
    then go wrong."""
    import jax.numpy as jnp

    from repro.kernels import ops

    orig = ops.dominance_matrix

    def weak(F, v=None, **k):
        same = (F[:, None, :] == F[None, :, :]).all(-1) & ~jnp.eye(F.shape[0], dtype=bool)
        return orig(F, v, **k) | same

    monkeypatch.setattr(ops, "dominance_matrix", weak)
    result, compared = _dse()
    assert not result["correct"]
    assert compared["misranked_points"]["value"] > 0


def test_dse_control_fails():
    """The numpy reference at bfloat16 put in the program's place: its
    objectives of the genomes a sound run returned go through the
    cell's own check, which has to find them not correct."""
    conf = _load("dse-smoke")
    c = conf["config"]
    scen = [tuple(x) for x in c["scenarios"]]
    results = nsga2.run_batched(
        ScenarioTable.from_specs(_normalize_scenarios(scen)),
        nsga2.NSGA2Config(pop_size=c["pop_size"], generations=c["generations"],
                          seed=2 ** 33 + 3))
    control = dse.control(scen, results)
    digest = dse._digest(control)
    compared = dse.check(conf, scen, control, [digest], digest)
    assert any(v["value"] > v["limit"] for v in compared.values())
    c = compared["max_rel_objective_error"]
    assert c["value"] > 3 * c["limit"]
