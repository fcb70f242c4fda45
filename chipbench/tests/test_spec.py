"""BENCHMARK.json against the contract's static rules, and the harness
finding everything by name."""

import hashlib
import json
import pathlib

import pytest

from chipbench.harness.spec import NAME_RE, UNIT_RE, Benchmark
from chipbench.tests.conftest import ROOT, add_tiny_cells, copy_checkout

TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}


@pytest.fixture(scope="module")
def bench():
    return Benchmark(ROOT)


def test_top_level_and_entry_keys(bench):
    d = bench.doc
    assert set(d) == TOP
    assert d["command"] == ["python3", "chipbench/run.py"]
    assert d["paths"] == ["chipbench"]
    for c in d["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("chipbench/")
    for w in d["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in d["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
    for m in d["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024


def test_bounds_and_window_fit_the_check(bench):
    d = bench.doc
    for m in d["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m["name"]
    rs = d["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
    four = sum(w["chips"] == 4 for w in d["workloads"])
    assert four <= max(1, len(d["workloads"]) // 2)


def test_names_and_units(bench):
    d = bench.doc
    for m in d["end_to_end"] + d["per_layer"]:
        assert NAME_RE.match(m["name"]) and UNIT_RE.match(m["unit"])
    for n in [c["name"] for c in d["configs"]] + \
            [w["name"] for w in d["workloads"]]:
        assert NAME_RE.match(n)
    layers = {m["layer"] for m in d["per_layer"]}
    assert all("\n" not in l and "\t" not in l for l in layers)


def test_every_cell_resolves_its_files(bench):
    used = set()
    for name in bench.workloads:
        cell = bench.cell(name)
        used.add(cell.config_name)
        assert bench.driver(cell).run
        for m in cell.end_to_end + cell.per_layer:
            assert callable(bench.reader(m.name))
    assert used == set(bench.configs)


def test_every_per_layer_metric_moves_one_reported_metric(bench):
    e2e = {m["name"] for m in bench.doc["end_to_end"]}
    for m in bench.doc["per_layer"]:
        assert m["moves"] in e2e
        for cell in m["workloads"]:
            assert m["moves"] in {x.name for x in bench.cell(cell).end_to_end}
    for name in bench.workloads:
        cell = bench.cell(name)
        names = {m.name for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2 and cell.per_layer


def test_no_contract_problems(bench):
    assert bench.problems() == []


def _digests(root: pathlib.Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_new_cells_come_from_new_files_only(tmp_path):
    root = copy_checkout(tmp_path / "checkout")
    before = _digests(root / "chipbench")
    add_tiny_cells(root)
    (root / "chipbench" / "metrics" / "served_tokens.py").write_text(
        "def read(run):\n    return run.data.get('served_tokens')\n")
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["per_layer"].append({
        "name": "served_tokens", "unit": "tokens", "better": "higher",
        "source": "host_clock", "layer": "serving engine",
        "moves": "itl_p50_ms", "workloads": ["tiny-lm.tinychat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    after = _digests(root / "chipbench")
    assert {k: after[k] for k in before} == before
    b = Benchmark(root)
    assert b.problems() == []
    cell = b.cell("tiny-lm.tinychat")
    assert cell.config["hidden_size"] == 64
    assert "served_tokens" in {m.name for m in cell.per_layer}
    assert b.cell("tiny-cnn.tinyring").traffic["management"] == \
        "kernel_level_ring"


def test_tiny_cells_join_serving_metrics_the_benchmark_holds(tmp_path):
    """Once BENCHMARK.json holds the serving metrics for an LM cell, the
    tiny serving cell joins their entries instead of shadowing them."""
    from chipbench.tests.conftest import SERVING_METRICS

    root = copy_checkout(tmp_path / "checkout")
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["configs"].append({
        "name": "h2o-danube-1.8b", "source": "test", "reduced": [],
        "why": "test", "file": "chipbench/configs/h2o-danube-1.8b.json"})
    doc["workloads"].append({
        "name": "h2o-danube-1.8b.chat", "config": "h2o-danube-1.8b",
        "traffic": "chat", "chips": 1, "why": "test"})
    for kind, m in SERVING_METRICS:
        doc[kind].append(dict(m, workloads=["h2o-danube-1.8b.chat"]))
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    add_tiny_cells(root)
    b = Benchmark(root)
    assert b.problems() == []
    names = [m["name"] for m in b.doc["end_to_end"] + b.doc["per_layer"]]
    assert len(names) == len(set(names))
    serving = {m["name"] for _kind, m in SERVING_METRICS} | {"setup_s"}
    for cell in ("h2o-danube-1.8b.chat", "tiny-lm.tinychat"):
        c = b.cell(cell)
        assert {m.name for m in c.end_to_end + c.per_layer} == serving
    for cell in ("roshambo.ring4", "roshambo.polling"):
        c = b.cell(cell)
        assert {m.name for m in c.end_to_end} == {
            "setup_s", "frames_per_s", "frame_p95_ms"}
        assert not {m.name for m in c.per_layer} & serving
