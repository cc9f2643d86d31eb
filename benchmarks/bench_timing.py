"""One-off instrumentation sitting: time every bench.py phase on the
real chip, with the persistent XLA compilation cache enabled, so round
5 can budget the driver's bench run (VERDICT r4 weak #1 / next #1).

Run twice: the first sitting is cold (populates the compilation cache,
deeplearning4j_tpu/util/compile_cache.py), the second shows what a
warm sitting would cost.

    python benchmarks/bench_timing.py
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

from deeplearning4j_tpu.util import compile_cache

compile_cache.enable()


def timed(name, fn):
    t0 = time.perf_counter()
    try:
        out = fn()
    except Exception as e:
        out = {"error": f"{type(e).__name__}: {e}"[:200]}
    dt = time.perf_counter() - t0
    print(json.dumps({"phase": name, "sec": round(dt, 1),
                      "out": out}), flush=True)


def lenet():
    import subprocess
    env = dict(os.environ, BENCH_FLAGSHIP="0")   # same cache: bench.py
    #                                              calls the same helper
    t0 = time.perf_counter()
    r = subprocess.run([sys.executable, "bench.py"], env=env,
                       capture_output=True, text=True,
                       cwd=os.path.join(os.path.dirname(
                           os.path.abspath(__file__)), ".."))
    dt = time.perf_counter() - t0
    line = [l for l in r.stdout.splitlines() if l.startswith("{")]
    print(json.dumps({"phase": "lenet_subprocess", "sec": round(dt, 1),
                      "out": line[-1] if line else r.stderr[-200:]}),
          flush=True)


def main():
    t_start = time.perf_counter()
    lenet()
    import flagship
    for name in ["transformer", "transformer_1024",
                 "transformer_32kvocab", "decode", "decode_long",
                 "vgg16", "lstm"]:
        timed(name, flagship.BENCHES[name])
    print(json.dumps({"phase": "TOTAL", "sec": round(
        time.perf_counter() - t_start, 1)}), flush=True)


if __name__ == "__main__":
    main()
