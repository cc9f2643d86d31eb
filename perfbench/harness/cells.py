"""Finds a cell's files by the names in BENCHMARK.json.

Nothing here knows a cell, a configuration, a traffic mix or a metric by
name. A later PR adds `configs/<config>.json`, `traffic/<traffic>.json`,
`metrics/<metric>.json` (naming a reader under `readers/`) and one entry
each in BENCHMARK.json, and edits no file that is there.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Any, Dict, List

ROOT = Path(__file__).resolve().parents[1]          # perfbench/
REPO = ROOT.parent
BENCHMARK = REPO / "BENCHMARK.json"

# the --rehearse dry run's model: CPU-sized, never a measurement
REHEARSAL_SIZES = dict(n_layer=2, n_embd=128, n_head=4, n_inner=512,
                       vocab_size=512, n_positions=256)


def enable_compile_cache() -> None:
    """JAX's persistent cache through the program's one helper: where the
    environment names a directory, there; else a fixed directory inside the
    checkout, `.cache/jax` (the path is part of the cache's key)."""
    import jax

    from deeplearning4j_tpu.util import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of `workloads`, with everything its names lead to."""

    def __init__(self, workload: str, benchmark: Path = None,
                 root: Path = None):
        root = root or ROOT
        self.benchmark = load_json(benchmark or BENCHMARK)
        by_name = {w["name"]: w for w in self.benchmark["workloads"]}
        if workload not in by_name:
            raise SystemExit(f"perfbench: no workload {workload!r} in "
                             f"BENCHMARK.json; have {sorted(by_name)}")
        self.entry = by_name[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        cfg_entry = next(c for c in self.benchmark["configs"]
                         if c["name"] == self.entry["config"])
        self.config = load_json(root.parent / cfg_entry["file"])
        self.traffic = load_json(
            root / "traffic" / f"{self.entry['traffic']}.json")
        self.root = root

    def sizes(self, rehearse: bool = False):
        from perfbench.harness.arith import Sizes
        return Sizes.from_file(dict(
            self.config, **(REHEARSAL_SIZES if rehearse else {})))

    def _lists(self, metric: dict) -> bool:
        return self.name in metric.get("workloads", [self.name])

    def end_to_end(self) -> List[dict]:
        return [m for m in self.benchmark["end_to_end"] if self._lists(m)]

    def per_layer(self) -> List[dict]:
        return [m for m in self.benchmark["per_layer"] if self._lists(m)]

    def reference(self):
        name = self.config["reference"]
        return load_module(self.root / "references" / f"{name}.py",
                           f"perfbench_reference_{name}")

    def read_per_layer(self, run: Dict[str, Any]) -> Dict[str, dict]:
        """Each per-layer metric of this cell through its own reader. A
        reader that finds nothing to read returns None and the metric is
        left out of the line."""
        out = {}
        for m in self.per_layer():
            spec = load_json(self.root / "metrics" / f"{m['name']}.json")
            reader = load_module(
                self.root / "readers" / f"{spec['reader']}.py",
                f"perfbench_reader_{spec['reader']}")
            value = reader.read(run, spec.get("args", {}))
            if value is not None:
                out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        return out
