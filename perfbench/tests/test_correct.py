"""`correct` has been shown to fail: the control (the plain reference in the
program's place at a lower precision) and each fault a cell can have come
out as not correct. These drive the rest of a run at a size a test can hold,
past the harness's look for a chip (`--rehearse`), with the timed path
broken underneath. The chip readings at the cells' own sizes are in PERF.md."""
import json
from pathlib import Path

import numpy as np
import pytest

import perfbench.run as runner
from perfbench.harness import cells


WAITING = cells.ROOT / "waiting.json"      # the serving cells, not yet in
                                           # BENCHMARK.json (PERF.md, 7)


def run_cell(capsys, workload, seed=7, seconds=3, benchmark=None):
    rc = runner.main(["--workload", workload, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", "0",
                      "--rehearse"] + (["--benchmark", str(benchmark)]
                                       if benchmark else []))
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_a_sound_training_run_is_correct(capsys):
    out = run_cell(capsys, "gpt2m-train-1chip")
    assert out["correct"] is True and out["failed"] == 0
    assert list(out)[-1] == "compared"
    assert set(out["compared"]) == {"loss_step1", "loss_step3",
                                    "grad_norm_gap", "update_norm_gap",
                                    "grad_direction_gap"}


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        capsys, monkeypatch):
    import jax

    import deeplearning4j_tpu.parallel.megatron as megatron
    real = megatron.make_parallel_train_step

    def broken(cfg, mesh, **kw):
        step = real(cfg, mesh, **kw)

        def unchanged(params, opt, tokens, targets):
            _, _, loss = step(*jax.tree_util.tree_map(
                lambda x: x.copy(), (params, opt)), tokens, targets)
            return params, opt, loss
        return unchanged
    monkeypatch.setattr(megatron, "make_parallel_train_step", broken)
    out = run_cell(capsys, "gpt2m-train-1chip")
    assert out["correct"] is False
    # the first gradient and the change both read 1: nothing moved
    assert out["compared"]["grad_norm_gap"]["value"] == pytest.approx(1, abs=0.05)
    assert out["compared"]["update_norm_gap"]["value"] == pytest.approx(1, abs=0.05)


def test_half_of_the_batch_left_out_is_not_correct(capsys, monkeypatch):
    import deeplearning4j_tpu.parallel.megatron as megatron
    real = megatron.make_parallel_train_step

    def broken(cfg, mesh, **kw):
        step = real(cfg, mesh, **kw)

        def half(params, opt, tokens, targets):
            n = tokens.shape[0] // 2
            return step(params, opt, tokens[:n], targets[:n])
        return half
    monkeypatch.setattr(megatron, "make_parallel_train_step", broken)
    out = run_cell(capsys, "gpt2m-train-1chip")
    assert out["correct"] is False


@pytest.fixture(scope="module")
def served():
    """One rehearsal-sized window of open-loop traffic on the served
    configuration, for the serving checks."""
    import gc

    from perfbench.harness import serve, traffic
    cell = cells.Cell("cgpt13-chat-short", benchmark=WAITING)
    s = cell.sizes(rehearse=True)
    tr = serve.shrink_traffic(cell.traffic)
    engine, _ = serve.build(cell, s, tr, 5, True, {})
    engine.start()
    gen = traffic.SERVING_KINDS[tr["kind"]](tr, s.vocab_size, 5)
    d = serve.drive(engine, gen, tr, 4.0, 1.0)
    engine.stop(drain=False)
    del engine
    gc.collect()
    sample = serve.pick_sample(d["log"], d["t0"], d["t1"], d["reqs"],
                               d["answers"], 6, 5)
    return cell.reference(), s, sample, tr["check"]


def test_what_the_engine_served_is_correct(served):
    from perfbench.harness import serve
    ref, s, sample, check = served
    assert len(sample) == 6
    # the longest finished request is in the sample
    assert len(sample[0][0]) + len(sample[0][1]) == max(
        len(p) + len(g) for p, g in sample)
    compared = serve.check_served(ref, s, sample, check, 5)
    assert serve.is_correct(compared)


def test_a_token_altered_where_it_is_produced_is_not_correct(
        capsys, monkeypatch):
    from deeplearning4j_tpu.serving.engine import InferenceEngine
    real = InferenceEngine._commit_tokens

    def altered(self, r, toks, kind, **data):
        if kind == "decode_chunk" and len(toks) > 2:
            toks = np.array(toks)
            toks[1] = (toks[1] + 1) % self.cfg.vocab_size
        return real(self, r, toks, kind, **data)
    monkeypatch.setattr(InferenceEngine, "_commit_tokens", altered)
    out = run_cell(capsys, "cgpt13-flood", seconds=4, benchmark=WAITING)
    assert out["correct"] is False
    assert out["compared"]["worst_gap_sd"]["value"] > 1.0


def test_the_control_is_not_correct(served):
    """The reference in the program's place at the next lower precision:
    the gap of the token that the lower precision puts first."""
    from perfbench.harness import serve
    ref, s, sample, check = served
    low = serve.check_served(ref, s, sample, check, 5,
                             precision=check["control"])
    assert not serve.is_correct(low)


def test_the_training_control_is_not_correct():
    import jax

    from perfbench.harness import train, traffic
    cell = cells.Cell("gpt2m-train-1chip")
    s = cell.sizes(rehearse=True)
    tr = train.shrink_traffic(cell.traffic)
    ref = cell.reference()
    tok, tgt = traffic.train_batches(tr, s.vocab_size, 3)
    batches = [(tok[i], tgt[i]) for i in range(3)]
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    want = train.reference_readings(ref, s, batches, tr, 3, mesh)
    low = train.reference_readings(ref, s, batches, tr, 3, mesh,
                                   precision=tr["check"]["control"])
    compared = train.compare_readings(low, want, tr["check"]["limits"])
    assert not train.is_correct(compared)


def test_sessions_run_through_the_engine():
    """The `sessions` kind end to end at rehearsal size: later turns are
    sent a think time after the answer, carry the conversation, and find
    their prefix in the engine's cache."""
    import gc
    from perfbench.harness import serve, stats, traffic
    cell = cells.Cell("cgpt13-flood", benchmark=WAITING)
    s = cell.sizes(rehearse=True)
    tr = json.loads((Path(__file__).resolve().parent / "data"
                     / "sessions-example.json").read_text())
    tr = serve.shrink_traffic(tr)
    tr.update(rate_per_s=2.0,
              think_s={"dist": "uniform", "min": 0.2, "max": 0.6})
    engine, _ = serve.build(cell, s, tr, 9, True, {})
    engine.start()
    gen = traffic.Sessions(tr, s.vocab_size, 9)
    d = serve.drive(engine, gen, tr, 6.0, 1.0)
    shared = engine.registry.get("serving_prefix_shared_tokens").value
    engine.stop(drain=False)
    del engine
    gc.collect()
    turns = [d["reqs"][sv.seq].turn for sv in d["log"]]
    assert max(turns) >= 2
    later = [sv for sv in d["log"] if d["reqs"][sv.seq].turn > 0]
    assert all(sv.sent - sv.due < 0.25 for sv in later)
    sm = stats.serving_summary(d["log"], d["t0"], d["t1"])
    assert sm["attempted"] > 5 and sm["failed"] == 0
    assert shared > 0
