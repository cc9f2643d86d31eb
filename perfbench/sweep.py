#!/usr/bin/env python3
"""Finds an open-loop cell's knee, once, on the chip.

    python3 perfbench/sweep.py --benchmark perfbench/waiting.json \
        --workload cgpt13-chat-short --rates 1.5,2.25,3,3.75,4.5 \
        --seconds 40 --seed 1

One process, one engine, five rates: each rate is offered for a ramp and a
window like a run's, then the engine is left to empty. The knee is the
highest rate at which the queue is no longer at the window's end than at its
start and nothing is shed or failed. The cell's traffic file then gets 0.8 of
it, as a number; this script changes no file. Prints one table row a rate,
and a JSON line last.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))


def queue_depth(samples, a: float, b: float) -> float:
    """Mean of the sampled `serving_queue_depth` gauge over [a, b]."""
    from perfbench.harness.serve import Client
    col = 1 + Client.GAUGES.index("serving_queue_depth")
    xs = [s[col] for s in samples if a <= s[0] <= b]
    return sum(xs) / len(xs) if xs else 0.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--benchmark", type=Path,
                    help="a file in BENCHMARK.json's shape, such as "
                         "perfbench/waiting.json, to find the cell in")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import os
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    from perfbench.harness import cells, serve, stats, traffic

    cell = cells.Cell(args.workload, benchmark=args.benchmark)
    dev = jax.devices()[0]
    if not args.rehearse and dev.platform != "tpu":
        print("perfbench/sweep.py: no TPU; nothing is measured on a CPU",
              file=sys.stderr)
        return 2
    cells.enable_compile_cache()
    sizes = cell.sizes(args.rehearse)
    base = (serve.shrink_traffic(cell.traffic) if args.rehearse
            else cell.traffic)
    if base["kind"] != "open_loop":
        raise SystemExit("a knee is swept for an open-loop cell")
    engine, _ = serve.build(cell, sizes, base, args.seed, args.rehearse, {})
    engine.start()
    rows = []
    head = ("rate/s", "queue@open", "queue@close", "due", "failed",
            "ttft_p50", "ttft_p75", "tpot_p50", "tpot_p90", "tokens/s",
            "sustained")
    print(" | ".join(head), flush=True)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        tr = dict(base, rate_per_s=rate)
        gen = traffic.SERVING_KINDS[tr["kind"]](tr, sizes.vocab_size,
                                                args.seed + i)
        d = serve.drive(engine, gen, tr, args.seconds, float(tr["ramp_s"]))
        sm = stats.serving_summary(d["log"], d["t0"], d["t1"])
        edge = min(5.0, args.seconds / 4)
        q0 = queue_depth(d["samples"], d["t0"], d["t0"] + edge)
        q1 = queue_depth(d["samples"], d["t1"] - edge, d["t1"])

        def pct(xs, q):
            return stats.percentile(xs, q) if xs else None
        ok = sm["failed"] == 0 and q1 <= q0 + 1.0
        row = {"rate_per_s": rate, "queue_open": q0, "queue_close": q1,
               "due": sm["attempted"], "failed": sm["failed"],
               "ttft_p50_ms": pct(sm["ttft_ms"], 50),
               "ttft_p75_ms": pct(sm["ttft_ms"], 75),
               "tpot_p50_ms": pct(sm["tpot_ms"], 50),
               "tpot_p90_ms": pct(sm["tpot_ms"], 90),
               "tokens_per_s": sm["tokens_per_s"], "sustained": ok}
        rows.append(row)
        print(" | ".join(f"{v:.4g}" if isinstance(v, float) else str(v)
                         for v in row.values()), flush=True)
        deadline = time.perf_counter() + 120
        while not engine.drained() and time.perf_counter() < deadline:
            time.sleep(0.05)
    engine.stop(drain=False)
    sustained = [r["rate_per_s"] for r in rows if r["sustained"]]
    knee = max(sustained) if sustained else None
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform,
                      "knee_per_s": knee,
                      "rate_at_0.8": None if knee is None else 0.8 * knee,
                      "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
