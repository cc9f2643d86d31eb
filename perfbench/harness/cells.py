"""Finds a cell's files by the names in BENCHMARK.json.

Nothing here knows a cell, a configuration, a traffic mix, a metric or a
size by name. A later PR adds files and entries and edits no file that is
there:

- `configs/<config>.json`: any keys; `arch` and `reference` name the two
  modules below, `reduced`, `assumed` and the deployment the cut stands for
  say where the sizes came from;
- `archs/<arch>.py`, where the benchmark binds to the program, one module a
  family of block. It owes the harness `sizes(cfg)` (a frozen, hashable
  object, a static argument of jitted reference steps, with at least
  `vocab_size`: the ids the traffic draws from and the loss is over),
  `rehearsal(cfg)` (the keys `--rehearse` overlays on the file, CPU-sized)
  and `program_config(cfg, sizes, **training)` (the program's config; every
  size in it comes from `sizes`, `cfg` gives what is not one, so that a
  rehearsal reaches the program whole); and each metric's reader the count
  of required work it asks for by name through `Cell.count`
  (`train_flops_per_step`, `forward_flops`, `flash_train_roofline_s`,
  `decode_attn_roofline_s`, ...): no recomputation, gathers, casts or copies;
- `references/<reference>.py`, the plain reference, which imports nothing
  of the program and takes the same `sizes`;
- `traffic/<traffic>.json` with its own `check` limits,
  `metrics/<metric>.json` naming a reader under `readers/` that is there or
  new, and entries in BENCHMARK.json (`configs`, `workloads`, `per_layer`,
  and the cell's name in its end-to-end metric's `workloads`).
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path
from typing import Any, Dict, List

ROOT = Path(__file__).resolve().parents[1]          # perfbench/
REPO = ROOT.parent
BENCHMARK = REPO / "BENCHMARK.json"


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
    sys.modules[name] = mod       # a dataclass looks its module up there
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
        self._arch = None

    def arch(self):
        """The family's module, loaded once a cell: its `Sizes` is one
        class for everything that this cell builds."""
        if self._arch is None:
            name = self.config["arch"]
            self._arch = load_module(self.root / "archs" / f"{name}.py",
                                     f"perfbench_arch_{name}")
        return self._arch

    def sizes(self, rehearse: bool = False):
        arch = self.arch()
        return arch.sizes(dict(
            self.config, **(arch.rehearsal(self.config) if rehearse else {})))

    def program_config(self, sizes, **training):
        return self.arch().program_config(self.config, sizes, **training)

    def count(self, name: str):
        """The arch's function of that name, a count of required work that
        a metric's reader asks for; an arch without it is an error."""
        fn = getattr(self.arch(), name, None)
        if fn is None:
            raise SystemExit(
                f"perfbench: archs/{self.config['arch']}.py has no "
                f"{name}(), which a metric of {self.name} asks of it")
        return fn

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
