"""A configuration, a traffic mix, a per-layer metric and a cell are each
added as new files plus one new entry, with no edit to a file that is
there."""
import hashlib
import json
import shutil
from pathlib import Path

import pytest

from perfbench.harness import cells

ROOT = Path(__file__).resolve().parents[1]


def digest(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts and ".cache" not in p.parts
            and ".out" not in p.parts}


def test_a_dummy_of_each_is_found_without_editing_a_file(tmp_path):
    copy = tmp_path / "repo"
    shutil.copytree(ROOT, copy / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", ".cache",
                                                  ".out"))
    before = digest(copy / "perfbench")
    bench = json.loads((ROOT.parent / "BENCHMARK.json").read_text())
    old_cell = bench["workloads"][0]["name"]

    pb = copy / "perfbench"
    cfg = json.loads((pb / "configs" / "cerebras-gpt-1.3b.json").read_text())
    cfg.update(name="dummy-model", n_layer=3)
    (pb / "configs" / "dummy-model.json").write_text(json.dumps(cfg))
    tr = json.loads((pb / "traffic" / "chat-short.json").read_text())
    tr.update(rate_per_s=9.0)
    (pb / "traffic" / "dummy-mix.json").write_text(json.dumps(tr))
    (pb / "metrics" / "dummy_metric.json").write_text(json.dumps(
        {"reader": "dummy_reader", "args": {"times": 3}}))
    (pb / "readers" / "dummy_reader.py").write_text(
        "def read(run, args):\n    return run['x'] * args['times']\n")
    (pb / "metrics" / "dummy_silent.json").write_text(json.dumps(
        {"reader": "device_idle_share", "args": {}}))

    bench["configs"].append({"name": "dummy-model", "source": "none",
                             "file": "perfbench/configs/dummy-model.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "dummy-cell", "config": "dummy-model",
                               "traffic": "dummy-mix", "chips": 1,
                               "why": "a test"})
    bench["end_to_end"].append(
        {"name": "dummy_rate", "unit": "tokens/s", "better": "higher",
         "bound": 0.01, "source": "host_clock", "workloads": ["dummy-cell"]})
    for name in ("dummy_metric", "dummy_silent"):
        bench["per_layer"].append(
            {"name": name, "unit": "count", "better": "higher",
             "source": "program_counter", "layer": "load generator",
             "moves": "dummy_rate", "workloads": ["dummy-cell"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = cells.Cell("dummy-cell", benchmark=copy / "BENCHMARK.json",
                      root=pb)
    assert cell.config["n_layer"] == 3
    assert cell.traffic["rate_per_s"] == 9.0
    names = [m["name"] for m in cell.per_layer()]
    assert "dummy_metric" in names and "compile_in_window" not in names
    assert [m["name"] for m in cell.end_to_end()] == ["setup_s",
                                                      "dummy_rate"]
    got = cell.read_per_layer({"x": 7.0})
    # a reader that finds nothing to read leaves its metric out of the line
    assert got == {"dummy_metric": {"value": 21.0, "unit": "count"}}
    # and the old cell is still found, with its own metrics and no new one
    old = cells.Cell(old_cell, benchmark=copy / "BENCHMARK.json", root=pb)
    assert old.traffic["kind"] == "train"
    assert "dummy_metric" not in [m["name"] for m in old.per_layer()]

    after = digest(pb)
    assert {k: v for k, v in after.items() if k in before} == before


@pytest.mark.parametrize("benchmark", ["BENCHMARK.json",
                                       "perfbench/waiting.json"])
def test_every_named_file_exists(benchmark):
    """In BENCHMARK.json, and in the file of the cells that wait."""
    path = ROOT.parent / benchmark
    bench = json.loads(path.read_text())
    reported = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        cell = cells.Cell(w["name"], benchmark=path)
        assert cell.per_layer() and len(cell.end_to_end()) >= 2
        for m in cell.per_layer():
            assert m["moves"] in reported
            spec = json.loads((ROOT / "metrics" / f"{m['name']}.json")
                              .read_text())
            assert (ROOT / "readers" / f"{spec['reader']}.py").is_file()
    for c in bench["configs"]:
        assert json.loads((ROOT.parent / c["file"]).read_text())["name"] == \
            c["name"]
        assert any(w["config"] == c["name"] for w in bench["workloads"])
