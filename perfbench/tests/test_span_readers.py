"""The readers of the program's spans and of its named kernels, each on a
reduced trace and a snapshot made up here, where every count and share is
known; then the files the new entries name, and one rehearsal through
`waiting_spans.json`."""
import gzip
import json
import shutil
import types
from pathlib import Path

import pytest

import perfbench.run as runner
from perfbench.harness import cells, xplane
from perfbench.harness.arith import PEAKS

from deeplearning4j_tpu.observability.tracing import Snapshot, Span

ROOT = cells.ROOT
SPANS = ROOT / "waiting_spans.json"
KIND = next(iter(PEAKS))


def reader(name):
    return cells.load_module(ROOT / "readers" / f"{name}.py", f"r_{name}")


def reduced(op_events, modules, window_s):
    red = xplane.Reduced()
    red.window_s = window_s
    red.devices.append({
        "name": "/device:TPU:0", "ops": {}, "modules": modules,
        "op_events": op_events,
        "busy_s": xplane.union_seconds(
            [(st, st + d) for _, _, st, d in op_events])})
    return red


# three executions of jit_step inside the window, the last cut short by the
# end of the profile, and another program beside them; the forward kernel
# runs twice a step, the backward once. A fusion that reads the kernel's
# output carries the kernel's name in its own.
FWD = "tpu_custom_call:flash_fwd_bf16_512_1024_64_"
BWD = "tpu_custom_call:flash_bwd_bf16_512_1024_64_"
OPS = [(FWD, "%flash_fwd.1 = ...", 0.0, 1.0),
       (FWD, "%flash_fwd.2 = ...", 2.0, 1.0),
       (BWD, "%flash_bwd.1 = ...", 3.0, 2.0),
       ("fusion_bf16_8_", "%fusion.3 = fusion(%flash_fwd.2)", 5.0, 4.0),
       (FWD, "%flash_fwd.1 = ...", 10.0, 1.0),
       (FWD, "%flash_fwd.2 = ...", 12.0, 1.0),
       (BWD, "%flash_bwd.1 = ...", 13.0, 2.0),
       ("fusion_bf16_8_", "%fusion.3 = fusion(%flash_fwd.2)", 15.0, 4.0),
       (FWD, "%flash_fwd.1 = ...", 20.0, 1.0),
       (FWD, "%flash_fwd.9 = ...", 30.0, 1.0)]
MODULES = [("jit_step(1)", 0.0, 9.5), ("jit_step(1)", 10.0, 9.5),
           ("jit_step(1)", 20.0, 1.5), ("jit_other(2)", 30.0, 1.0)]


def test_kernel_calls_and_shares_by_name():
    run = {"trace": reduced(OPS, MODULES, 40.0)}
    calls, share = reader("op_calls_per_run"), reader("op_share")
    step = {"module": "jit_step"}
    assert calls.read(run, dict(step, op="^tpu_custom_call:flash_fwd_")) == 2
    assert calls.read(run, dict(step, op="^tpu_custom_call:flash_bwd_")) == 1
    # a key only: the fusion that names the kernel among its operands is
    # not the kernel
    assert calls.read(run, dict(step, op="flash_fwd")) == 2
    # the last forward belongs to another program
    assert calls.read(run, {"module": "jit_other", "op": "flash_fwd"}) == 1
    # busy 18 s, forward 6 of them, backward 4
    fwd = share.read(run, {"op": "^tpu_custom_call:flash_fwd_"})
    bwd = share.read(run, {"op": "^tpu_custom_call:flash_bwd_"})
    assert fwd == pytest.approx(100 * 6 / 18)
    assert bwd == pytest.approx(100 * 4 / 18)
    assert share.read(run, {"op": "^tpu_custom_call:"}) == pytest.approx(
        fwd + bwd)
    # a program that has no such kernel (the parent) reads nothing
    assert calls.read(run, dict(step, op="^tpu_custom_call:nothing")) is None
    assert share.read(run, {"op": "^tpu_custom_call:nothing"}) is None
    assert share.read({}, {"op": "."}) is None


def test_kernel_readers_on_the_recorded_trace(tmp_path):
    """The v5e trace beside this file: two layers, so two of each kernel a
    step, under the names its kernels had then."""
    path = tmp_path / "t.xplane.pb"
    with gzip.open(Path(__file__).parent / "data"
                   / "train-tiny-v5e.xplane.pb.gz", "rb") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    red = xplane.reduce(path)
    run = {"trace": red}
    for k in ("closed_call", "rematted_computation", "checkpoint"):
        assert reader("op_calls_per_run").read(
            run, {"module": "jit_step",
                  "op": f"^tpu_custom_call:{k}_"}) == 2
    assert reader("op_share").read(run, {"op": "^tpu_custom_call:"}) == \
        pytest.approx(100 * red.op_seconds("^tpu_custom_call:") / red.busy_s,
                      rel=0.01)


def span(i, parent, name, start, end, tick=None, **args):
    return Span(i, parent, name, start, end, tick, None, args)


def ticks():
    """Three scheduling rounds: 100-101, 102-104, 110-112 on the host's
    clock. The second recovers a failed tick, re-prefills and blocks on
    pages twice; the third lies outside the window."""
    out, i = [], iter(range(1, 100))
    for tick, (a, b, sync) in enumerate(
            [(100.0, 101.0, 0.75), (102.0, 104.0, 1.0),
             (110.0, 112.0, 0.5)], 1):
        t = next(i)
        out.append(span(t, None, "engine.tick", a, b, tick, queue=3))
        d = next(i)
        out.append(span(d, t, "engine.tick.dispatch", a, a + 0.1, tick))
        out.append(span(next(i), d, "engine.dispatch.prefill", a, a + 0.05,
                        tick, program="paged_chunked_prefill", rows=1,
                        prefill_tokens=10 * tick, prefill_pairs=55 * tick,
                        reprefill_tokens=7 if tick == 2 else 0))
        out.append(span(next(i), d, "engine.dispatch.decode", a + 0.05,
                        a + 0.1, tick, program="paged_decode", rows=2,
                        steps=8, decode_tokens=16, decode_rows=1000 * tick))
        c = next(i)
        out.append(span(c, t, "engine.tick.commit", a + 0.1, a + 0.1 + sync,
                        tick, commits_tick=tick - 1, tokens=16))
        out.append(span(next(i), c, "engine.tick.commit.sync", a + 0.1,
                        a + 0.1 + sync, tick))
        if tick == 2:
            out.append(span(next(i), c, "engine.tick.recover", 103.5, 103.9,
                            tick, requests=2, error="RuntimeError"))
            for _ in range(2):
                out.append(span(next(i), t, "engine.admit.blocked", 102.01,
                                102.01, tick, reason="pages"))
            out.append(span(next(i), t, "engine.admit.blocked", 102.02,
                            102.02, tick, reason="slots"))
    return tuple(out)


def serving_run(**more):
    tracer = types.SimpleNamespace(t_a=101.5, t_b=105.0)
    return dict({"t0": 99.0, "t1": 105.0, "tracer": tracer,
                 "span_snapshot": Snapshot(ticks(), (0.0, 0))}, **more)


def test_counts_in_and_out_of_the_window():
    run, count = serving_run(), reader("span_count")
    assert count.read(run, {"name": "engine.tick"}) == 2
    assert count.read(run, {"name": "engine.tick", "window": "traced"}) == 1
    assert count.read(run, {"name": "engine.tick.recover"}) == 1
    assert count.read(run, {"name": "engine.dispatch.prefill"}) == 2
    blocked = {"name": "engine.admit.blocked", "where": {"reason": "pages"}}
    assert count.read(run, blocked) == 2
    assert count.read(run, dict(blocked, distinct="tick")) == 1
    # the loop ran and made none: nought, which is a reading
    assert count.read(run, {"name": "engine.never"}) == 0
    # no span at all in the window, or no ring: nothing to read
    assert count.read(dict(run, t0=0.0, t1=50.0),
                      {"name": "engine.tick"}) is None
    assert count.read(dict(run, tracer=None),
                      {"name": "engine.tick", "window": "traced"}) is None


def test_sums_self_times_and_shares():
    run = serving_run()
    total = reader("span_arg_sum")
    assert total.read(run, {"name": "engine.dispatch.prefill",
                            "arg": "reprefill_tokens"}) == 7
    assert total.read(run, {"name": "engine.dispatch.prefill",
                            "arg": "prefill_tokens"}) == 30
    # each tick less the sync two levels below it: 0.25 s and 1.0 s
    own = reader("span_self_ms")
    args = {"name": "engine.tick", "less": "engine.tick.commit.sync"}
    assert own.read(run, dict(args, q=0)) == pytest.approx(250.0)
    assert own.read(run, dict(args, q=100)) == pytest.approx(1000.0)
    assert own.read(run, dict(args, q=50)) == pytest.approx(625.0)
    # less the dispatch calls as well, which can wait on the device too
    both = dict(args, less=["engine.tick.commit.sync",
                            "engine.tick.dispatch"], q=0)
    assert own.read(run, both) == pytest.approx(150.0)
    assert own.read(run, dict(args, name="engine.never")) is None
    share = reader("span_share")
    assert share.read(run, {"part": "engine.tick.commit.sync",
                            "whole": "engine.tick"}) == pytest.approx(
        100 * 1.75 / 3.0)
    assert share.read(run, {"part": ["engine.tick.commit.sync",
                                     "engine.tick.dispatch"],
                            "whole": "engine.tick"}) == pytest.approx(
        100 * 1.95 / 3.0)
    assert share.read(run, {"part": "engine.tick.commit.sync",
                            "whole": "engine.never"}) is None


def test_mfu_pairs_device_time_with_the_tokens_dispatched_there():
    cell = cells.Cell("cgpt13-flood", benchmark=ROOT / "waiting.json")
    s, forward_flops = cell.sizes(rehearse=True), cell.count("forward_flops")
    red = reduced([], [("jit_run_paged_decode(7)", 0.0, 0.25),
                       ("jit_run_paged_chunked_prefill(8)", 0.25, 0.5)], 2.0)
    run = serving_run(trace=red, sizes=s, device_kind=KIND, cell=cell)
    mfu = reader("mfu_by_tick")
    peak = PEAKS[KIND]["flops_per_s"]
    # the traced window holds the second tick alone
    dec = forward_flops(s, 16, 2000)
    pre = forward_flops(s, 20, 110)
    assert mfu.read(run, {"tokens": "decode",
                          "module": "jit_run_paged_decode"}) == \
        pytest.approx(100 * dec / (0.25 * peak))
    assert mfu.read(run, {"tokens": "all"}) == pytest.approx(
        100 * (dec + pre) / (2.0 * peak))
    assert mfu.read(run, {"tokens": "decode", "module": "jit_nothing"}) \
        is None
    assert mfu.read(dict(run, trace=None), {"tokens": "all"}) is None


def idle_run(ops):
    """A traced window of 3.5 s that opens at 101.5 on the host's clock
    and at second 7.0 of the trace's, which counts from the profile's
    start."""
    return serving_run(trace=reduced(
        [("fusion", "%fusion.1", st, d) for st, d in ops], [], 3.5))


def test_idle_gaps_inside_and_outside_a_span(capsys):
    idle = reader("idle_by_span")
    # the device works 101.5-102.5, 103.0-104.5 and 104.6-105.0: idle
    # 0.5 s inside the second tick's sync, 0.1 s after that tick has ended
    run = idle_run([(7.0, 1.0), (8.5, 1.5), (10.1, 0.4)])
    assert idle.read(run, {}) == pytest.approx(100 * 0.5 / 0.6)
    err = capsys.readouterr().err
    assert "idle 0.000000 s at the traced window's edges" in err
    assert "idle 0.500000 s inside engine.tick.commit.sync" in err
    assert "idle 0.100000 s inside (no span)" in err
    # idle at both of the window's edges as well, 0.2 s each, between
    # ticks: the operations still land where they ran
    run = idle_run([(7.2, 0.8), (8.5, 1.5), (10.1, 0.2)])
    assert idle.read(run, {}) == pytest.approx(100 * 0.5 / 1.0)
    err = capsys.readouterr().err
    assert "idle 0.400000 s at the traced window's edges" in err
    assert "idle 0.500000 s inside engine.tick.commit.sync" in err
    assert "idle 0.500000 s inside (no span)" in err
    assert idle.read(dict(run, trace=None), {}) is None
    assert idle.read(dict(run, tracer=None), {}) is None


def test_a_program_without_the_ring_reads_nothing(monkeypatch):
    from deeplearning4j_tpu.observability import tracing
    monkeypatch.delattr(tracing, "default_spans")
    run = {"t0": 0.0, "t1": 1.0}
    for name, args in (("span_count", {"name": "engine.tick"}),
                       ("span_arg_sum", {"name": "x", "arg": "y"}),
                       ("span_self_ms", {"name": "x", "less": "y"}),
                       ("span_share", {"part": "x", "whole": "y"})):
        assert reader(name).read(run, args) is None


@pytest.mark.parametrize("benchmark", [cells.BENCHMARK, SPANS])
def test_every_file_the_new_entries_name_exists(benchmark):
    bench = json.loads(benchmark.read_text())
    reported = {m["name"] for m in bench["end_to_end"]}
    names = [m["name"] for m in bench["per_layer"]]
    assert len(names) == len(set(names))
    for m in bench["per_layer"]:
        assert m["moves"] in reported
        assert set(m["workloads"]) <= {w["name"] for w in bench["workloads"]}
        spec = json.loads((ROOT / "metrics" / f"{m['name']}.json").read_text())
        assert (ROOT / "readers" / f"{spec['reader']}.py").is_file()
    if benchmark == SPANS:
        waiting = json.loads((ROOT / "waiting.json").read_text())
        for key in ("configs", "workloads", "end_to_end", "run_seconds"):
            assert bench[key] == waiting[key]
        assert not set(names) & {m["name"] for m in waiting["per_layer"]}
    else:
        assert {"flash_fwd_calls_per_step.train", "flash_fwd_share.train",
                "flash_bwd_share.train"} <= set(names)


def test_a_rehearsal_through_waiting_spans_returns_every_count(capsys):
    rc = runner.main(["--workload", "cgpt13-flood", "--seed", "2147484777",
                      "--seconds", "3", "--trace", "1", "--rehearse",
                      "--benchmark", str(SPANS)])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True and out["rehearsal"] is True
    got = out["metrics"]
    for name in ("engine_ticks_recovered.batch",
                 "engine_admission_blocked_ticks.batch",
                 "engine_reprefill_tokens.batch",
                 "engine_prefill_calls.batch"):
        assert got[name]["value"] >= 0
    assert got["engine_prefill_calls.batch"]["value"] > 0
    assert got["engine_ticks_recovered.batch"]["value"] == 0
    # no device, so no device metric: a CPU run writes none
    assert not {"serve_step_mfu_by_tick.batch",
                "device_idle_spanned_share.batch"} & set(got)
