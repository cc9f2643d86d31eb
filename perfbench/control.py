#!/usr/bin/env python3
"""Reads, on the chip and at the cell's own size, the numbers that the
`correct` comparison compares: for the program as the configuration states
(the lower reading), for its control (the plain reference put in the
program's place at the next lower precision) and, for a training cell, for
the fault 'half of the batch left out, the mean taken over the rest' (the
upper readings). One process, several seeds. The limits in the traffic
files were set from this script's output (PERF.md lists the readings); the
benchmark's own runs do not run it.

    python3 perfbench/control.py --workload <name> --seeds 1,2,3,4,5,6 \
        --control-seeds 3 [--seconds 25]
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
PRECISIONS = ("int8", "fp8")


def values(compared: dict) -> dict:
    out = {k: v["value"] for k, v in compared.items()}
    out.update({f"{k}_leaf": v["leaf"] for k, v in compared.items()
                if "leaf" in v})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--benchmark", type=Path,
                    help="a file in BENCHMARK.json's shape, such as "
                         "perfbench/waiting.json, to find the cell in")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    from perfbench.harness import cells, serve, train, traffic

    cell = cells.Cell(args.workload, benchmark=args.benchmark)
    dev = jax.devices()[0]
    if not args.rehearse and dev.platform != "tpu":
        print("perfbench/control.py: no TPU", file=sys.stderr)
        return 2
    cells.enable_compile_cache()
    s = cell.sizes(args.rehearse)
    ref = cell.reference()
    names = ref.leaf_names(s)
    seeds = [int(x) for x in args.seeds.split(",")]
    training = cell.traffic["kind"] == "train"
    tr = cell.traffic
    if args.rehearse:
        tr = (train if training else serve).shrink_traffic(tr)

    for n, seed in enumerate(seeds):
        t = time.perf_counter()
        row = {"workload": cell.name, "seed": seed,
               "device": dev.device_kind}
        with_controls = n < args.control_seeds
        if training:
            step, params, opt, init, mesh = train._build(cell, tr, s, seed,
                                                         ref)
            tokens, targets = traffic.train_batches(tr, s.vocab_size, seed)
            batches = [(tokens[i], targets[i]) for i in range(3)]
            got = train.first_steps(ref, step, params, opt, batches, init,
                                    seed)
            losses = got["losses"]
            del params, opt, step
            got.pop("state")
            gc.collect()
            want = train.reference_readings(ref, s, batches, tr, seed, mesh)
            limits = tr["check"]["limits"]
            row["program"] = values(train.compare_readings(got, want,
                                                           limits, names))
            row["losses"] = {"program": losses, "reference": want["losses"]}
            if with_controls:
                for p in PRECISIONS:
                    ctl = train.reference_readings(ref, s, batches, tr, seed,
                                                   mesh, precision=p)
                    row[f"control_{p}"] = values(
                        train.compare_readings(ctl, want, limits, names))
                flt = train.reference_readings(ref, s, batches, tr, seed,
                                               mesh, halve_batch=True)
                row["fault_half_batch"] = values(
                    train.compare_readings(flt, want, limits, names))
        else:
            engine, _ = serve.build(cell, s, tr, seed, args.rehearse, {})
            engine.start()
            gen = traffic.SERVING_KINDS[tr["kind"]](tr, s.vocab_size, seed)
            d = serve.drive(engine, gen, tr, args.seconds,
                            float(tr["ramp_s"]) / 2)
            engine.stop(drain=False)
            del engine
            gc.collect()
            sample = serve.pick_sample(d["log"], d["t0"], d["t1"], d["reqs"],
                                       d["answers"],
                                       int(tr["check"]["sample"]), seed,
                                       tr.get("count", "due"))
            row["program"] = values(serve.check_served(
                ref, s, sample, tr["check"], seed))
            if with_controls:
                for p in PRECISIONS:
                    row[f"control_{p}"] = values(serve.check_served(
                        ref, s, sample, tr["check"], seed, precision=p))
        row["seconds"] = round(time.perf_counter() - t, 1)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
