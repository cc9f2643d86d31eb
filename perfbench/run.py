#!/usr/bin/env python3
"""perfbench/run.py: one run of one cell of BENCHMARK.json.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Runs only on the accelerator the cell asks for: with no TPU, or fewer chips
than the cell needs, it exits non-zero and prints no result. `--rehearse` is
the one exception: a dry run on the CPU at toy sizes, which says
`platform: cpu`, writes no device metric and is never a measurement.

The last line of standard output is one JSON object: `correct`, `attempted`,
`failed`, `metrics`, `device`, with `--trace 1` `breakdown`, and last
`compared`, each number that decided `correct` beside its limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

class CompileCounter:
    """Compilations as JAX itself reports them (`jax.monitoring`), stamped
    with the host's clock, so that those inside the window can be counted."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.times = []
        self.marks = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.times.append(time.perf_counter())

    def mark(self, name: str) -> None:
        self.marks[name] = time.perf_counter()

    def in_window(self) -> int:
        a, b = self.marks["open"], self.marks["close"]
        return sum(a < t <= b for t in self.times)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, toy sizes; proves nothing about the chip")
    ap.add_argument("--benchmark", type=Path,
                    help="a file in BENCHMARK.json's shape to find the cell "
                         "in instead, such as perfbench/waiting.json")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if not (HERE.parent / "deeplearning4j_tpu").is_dir():
        print("perfbench: the program (deeplearning4j_tpu/) is not in this "
              "checkout", file=sys.stderr)
        return 2
    from perfbench.harness import arith, cells
    cell = cells.Cell(args.workload, benchmark=args.benchmark)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if cell.chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") +
                f" --xla_force_host_platform_device_count={cell.chips}")

    import jax
    split = {"import": time.perf_counter() - T_START}
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"perfbench: no accelerator: {e}", file=sys.stderr)
        return 2
    split["backend_init"] = time.perf_counter() - T_START - split["import"]
    dev = devices[0]
    if not args.rehearse and dev.platform != "tpu":
        print(f"perfbench: JAX's first device is {dev.platform!r}, not a "
              "TPU; nothing here is measured on a CPU (see --rehearse)",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"perfbench: {cell.name} needs {cell.chips} chips, JAX has "
              f"{len(devices)}", file=sys.stderr)
        return 2
    if not args.rehearse:
        arith.peaks(dev.device_kind)            # unknown device: an error
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips}

    out_dir = HERE / ".out"
    out_dir.mkdir(exist_ok=True)
    cells.enable_compile_cache()
    ctx = {"t_start": T_START, "split": split,
           "sizes": cell.sizes(args.rehearse),
           "compiles": CompileCounter(), "out_dir": out_dir}
    kind = cell.traffic["kind"]
    if kind == "train":
        from perfbench.harness import train as driver
    else:
        from perfbench.harness import serve as driver
    run = driver.run(cell, args, ctx)
    run.update(cell=cell, ctx=ctx, device_kind=dev.device_kind,
               compile_in_window=ctx["compiles"].in_window(),
               rehearse=args.rehearse)

    device["memory_peak_bytes"] = run["memory_peak_bytes"]
    metrics, breakdown = {}, None
    if args.trace:
        from perfbench.harness import xplane
        trace_dir = out_dir / f"trace-{cell.name}"
        if not args.rehearse:
            run["trace"] = red = xplane.reduce(xplane.find_xplane(trace_dir))
            device["busy_s"], device["window_s"] = red.busy_s, red.window_s
            breakdown = red.breakdown()
        shutil.rmtree(trace_dir, ignore_errors=True)
        metrics = cell.read_per_layer(run)
    else:
        from perfbench.harness import end_to_end
        for m in cell.end_to_end():
            value = end_to_end.read(m["name"], run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}

    compared = run["compared"]
    correct = bool(driver.is_correct(compared))
    summary = run["summary"]
    split["reference_after_window"] = ctx["reference_s"]
    print("perfbench: setup_s splits as " + json.dumps(
        {k: round(v, 3) for k, v in split.items()}) +
        f"; window {summary['window_s']:.3f} s; whole run "
        f"{time.perf_counter() - T_START:.1f} s", flush=True)
    result = {"correct": correct, "attempted": int(summary["attempted"]),
              "failed": int(summary["failed"]), "metrics": metrics,
              "device": device}
    if args.rehearse:
        result["rehearsal"] = True
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    for name, c in compared.items():
        print(f"perfbench: compared {name} = {c['value']!r} "
              f"(limit {c['limit']!r})", file=sys.stderr)
    print(f"perfbench: correct = {correct}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
