"""``BENCHMARK.json`` holds together: every cell's configuration, traffic
mix and runner load, every per-layer metric has its reader, every
``workloads`` list names cells that exist, and every serving cell is
judged by the serving end-to-end metrics in ``SERVING``.  ``problems`` is
the check; the tests run it on the committed manifest and on broken copies
of it, which it has to refuse."""
import copy
import importlib
import json
import pathlib
import re

import pytest

from bench import gen, harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
# Every serving cell is judged by its token rate.  The latency tails are
# judged only in the cells whose runs hold a bound on them (PERF.md gives
# the readings); the runner leaves a tail the manifest does not list for a
# cell out of that cell's result line.
SERVING = ("output_tokens_per_s",)
TAILS = ("ttft_p95_ms", "itl_p95_ms")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _serving_cells(bench):
    files = {c["name"]: json.loads((ROOT / c["file"]).read_text()) for c in bench["configs"]}
    return [w["name"] for w in bench["workloads"] if files[w["config"]]["runner"] == "serve"]


def problems(bench: dict) -> list[str]:
    """Every way ``bench`` fails to hold together, as plain sentences."""
    out = []
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    metrics = bench["end_to_end"] + bench["per_layer"]
    for kind, items in (("cell", bench["workloads"]), ("config", bench["configs"]),
                        ("metric", metrics)):
        names = [x["name"] for x in items]
        out += [f"{kind} {n!r} appears twice" for n in set(names) if names.count(n) > 1]
        out += [f"{kind} name {n!r} is not a valid name" for n in names if not NAME.match(n)]
    for w in bench["workloads"]:
        conf = configs.get(w["config"])
        if conf is None:
            out.append(f"cell {w['name']!r}: no configuration {w['config']!r}")
            continue
        path = ROOT / conf["file"]
        if not path.is_file():
            out.append(f"cell {w['name']!r}: no file {conf['file']}")
            continue
        c = json.loads(path.read_text())
        try:
            importlib.import_module(f"bench.{c['runner']}").run
        except (KeyError, ImportError, AttributeError) as e:
            out.append(f"cell {w['name']!r}: runner does not load ({e!r})")
        if not (harness.BENCH / "traffic" / f"{w['traffic']}.json").is_file():
            out.append(f"cell {w['name']!r}: no traffic file {w['traffic']!r}")
    for m in metrics:
        for cell in m.get("workloads", []):
            if cell not in cells:
                out.append(f"metric {m['name']!r} lists {cell!r}, which is no cell")
    for m in bench["per_layer"]:
        path = harness.BENCH / "metrics" / f"{m['name']}.py"
        if not path.is_file() or not callable(getattr(harness.load("metrics", m["name"]),
                                                      "read", None)):
            out.append(f"per-layer metric {m['name']!r} has no reader at {path.name}")
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        conf = configs.get(w["config"])
        if conf is None or not (ROOT / conf["file"]).is_file():
            continue
        if json.loads((ROOT / conf["file"]).read_text()).get("runner") != "serve":
            continue
        for name in SERVING:
            m = e2e.get(name)
            if m is None or w["name"] not in m.get("workloads", [w["name"]]):
                out.append(f"serving cell {w['name']!r} is not judged by {name!r}")
    return out


def test_committed_manifest_holds_together():
    assert problems(MANIFEST) == []


def test_every_cell_has_setup_another_end_to_end_and_a_per_layer_metric():
    for w in MANIFEST["workloads"]:
        def lists(m, cell=w["name"]):
            return cell in m.get("workloads", [cell])
        e2e = [m["name"] for m in MANIFEST["end_to_end"] if lists(m)]
        assert "setup_s" in e2e and len(e2e) >= 2, (w["name"], e2e)
        assert any(lists(m) for m in MANIFEST["per_layer"]), w["name"]


def test_every_moved_metric_is_reported_where_the_layer_metric_is():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    for m in MANIFEST["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", []):
            assert cell in moved.get("workloads", [cell]), (m["name"], cell)


@pytest.mark.parametrize("cell", _serving_cells(MANIFEST))
def test_traffic_generates(cell):
    """Each serving cell's mix, through the one generator, at the
    manifest's window: a seed above 32 bits gives requests that fit
    ``max_len``."""
    w = next(x for x in MANIFEST["workloads"] if x["name"] == cell)
    conf = next(c for c in MANIFEST["configs"] if c["name"] == w["config"])
    c = json.loads((ROOT / conf["file"]).read_text())
    mix = harness.read_json("traffic", f"{w['traffic']}.json")
    sessions = gen.generate(mix, 2 ** 33 + 1, MANIFEST["run_seconds"], c["config"]["vocab_size"])
    asks = [a for s in sessions for a in s]
    assert asks
    assert max(len(a.prompt) + a.n_tokens for a in asks) <= c["serve"]["max_len"]


def test_serving_cells_exist():
    assert {"qwen2.5-3b.chat", "qwen2.5-3b.docqa"} <= set(_serving_cells(MANIFEST))


@pytest.mark.parametrize("cell", _serving_cells(MANIFEST))
def test_result_line_holds_just_the_cells_end_to_end_metrics(cell):
    readings = {n: {"value": 1.0, "unit": "u"} for n in SERVING + TAILS + ("setup_s",)}
    kept = harness.end_to_end_metrics(MANIFEST, cell, readings)
    listed = {m["name"] for m in MANIFEST["end_to_end"]
              if cell in m.get("workloads", [cell])}
    assert set(SERVING) | {"setup_s"} <= set(kept)
    for tail in TAILS:
        assert (tail in kept) == (tail in listed), (cell, tail)


def test_metric_naming_a_missing_cell_fails():
    bad = copy.deepcopy(MANIFEST)
    m = next(m for m in bad["end_to_end"] if m["name"] == "itl_p95_ms")
    m["workloads"] = m["workloads"] + ["qwen2.5-3b.nosuchcell"]
    assert any("nosuchcell" in p for p in problems(bad))


@pytest.mark.parametrize("cell", _serving_cells(MANIFEST))
def test_serving_cell_left_out_of_a_serving_metric_fails(cell):
    bad = copy.deepcopy(MANIFEST)
    m = next(m for m in bad["end_to_end"] if m["name"] == "output_tokens_per_s")
    m["workloads"] = [c for c in m["workloads"] if c != cell]
    assert any(cell in p and "output_tokens_per_s" in p for p in problems(bad))


def test_per_layer_metric_without_reader_fails():
    bad = copy.deepcopy(MANIFEST)
    bad["per_layer"].append({**bad["per_layer"][0], "name": "no_such.reader"})
    assert any("no_such.reader" in p for p in problems(bad))


def test_cell_with_missing_traffic_fails():
    bad = copy.deepcopy(MANIFEST)
    bad["workloads"][0] = {**bad["workloads"][0], "traffic": "no_such_mix"}
    assert any("no_such_mix" in p for p in problems(bad))


def test_cell_with_missing_configuration_fails():
    bad = copy.deepcopy(MANIFEST)
    bad["workloads"][0] = {**bad["workloads"][0], "config": "no_such_config"}
    assert any("no_such_config" in p for p in problems(bad))
