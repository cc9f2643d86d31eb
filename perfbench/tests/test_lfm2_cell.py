"""The LFM2-24B-A2B configuration's files: its counts pinned and worked by
hand, its metrics on a made-up reduced trace and a made-up index of the
program's operations, the cell's rehearsal, the configuration against the
catalog's row, and that what the benchmark had is untouched.

(The counts are pinned here and not as further cases of `test_arith.py`'s
parametrised test: a PR that adds a configuration edits no file the
benchmark has.)"""
import hashlib
import json
import subprocess
from pathlib import Path

import pytest

from perfbench.harness import arith, cells, scopes, xplane

ROOT = Path(__file__).resolve().parents[1]
CELL = "lfm2a2b-train-ep8share-1chip"
KIND = "TPU v5 lite"
ROWS, SEQ = 4, 8192
PARENT = "408bb8a8fb030da464057ef92ec8f9b2379e8118"

PINNED = {
    "held_params": ((), 469285248),
    "matmul_params": ((), 186122240.0),
    "train_flops_per_step": ((ROWS, SEQ), 39892058898432.0),
    "flash_train_roofline_s": ((ROWS, SEQ, KIND), 0.01674587581985787),
    "moe_gmm_train_roofline_s": ((ROWS, SEQ, KIND), 0.01883681088194924),
    "moe_live_rows": ((ROWS * SEQ,), 16384.0),
}


@pytest.fixture(scope="module")
def cell():
    return cells.Cell(CELL)


@pytest.mark.parametrize("count", sorted(PINNED))
def test_the_counts_are_pinned(cell, count):
    args, want = PINNED[count]
    got = getattr(cell.arch(), count)(cell.sizes(), *args)
    assert got == want and type(got) is type(want)


def test_the_traffic_is_what_the_issue_fixed(cell):
    """The parameters fixed before any reading: Adam at a full
    fine-tune's 1e-5, four packed rows of 8192, a block of eight, the dense
    loss, full remat a layer, a 4 s trace."""
    tr = cell.traffic
    assert (tr["entry"], tr["learning_rate"], tr["rows"], tr["seq"],
            tr["block"], tr["xent_chunk"], tr["remat"], tr["remat_policy"],
            tr["trace_s"], tr["eot_id"]) == (
        "megatron", 1e-5, 4, 8192, 8, 0, True, "full", 4, 8191)
    assert tr["doc_len"] == {"dist": "lognormal", "median": 1000,
                             "sigma": 1.0, "min": 16, "max": 8192}


def test_the_counts_by_hand(cell):
    a, s = cell.arch(), cell.sizes()
    assert (s.period, s.n_periods, s.lead_types, s.head_dim) == (
        ("full_attention", "conv", "conv", "conv"), 1, ("conv",), 64)
    conv_mm, attn_mm = 2048 * 6144 + 2048 * 2048, 2 * 2048 * 2048 + \
        2 * 2048 * 512
    dense = 3 * 2048 * 11776
    expert = 3 * 2048 * 1536
    # a token meets the router and 4 x 8 / 64 = half an expert
    moe = 2048 * 64 + expert // 2
    main = (conv_mm + dense) + 3 * (conv_mm + moe) + (attn_mm + moe) \
        + 2048 * 8192
    assert a.matmul_params(s) == main == 186122240
    n = ROWS * SEQ
    pairs = ROWS * arith.causal_pairs(SEQ)
    attn = 2 * 2 * 32 * 64 * pairs
    assert a.train_flops_per_step(s, ROWS, SEQ) == pytest.approx(
        3 * (2 * main * n + attn))
    # the step's split: products 36.6, attention 3.3, the four short-conv
    # mixers' projections 13.2 TFLOP
    assert 3 * 2 * main * n == pytest.approx(36.59e12, rel=1e-3)
    assert 3 * attn == pytest.approx(3.30e12, rel=1e-3)
    assert 4 * 3 * 2 * conv_mm * n == pytest.approx(13.19e12, rel=1e-3)
    # attention is FLOP-bound: q, o 32 x 64 wide, k, v 8 x 64
    nbytes = 6 * (2048 + 512) * n * 2
    assert 3 * attn / 197e12 > nbytes / 819e9
    assert a.flash_train_roofline_s(s, ROWS, SEQ, KIND) == pytest.approx(
        3 * attn / 197e12)
    # the routed experts: 8 x 2,048 live rows a layer, FLOP-bound
    assert a.moe_live_rows(s, n) == 8 * 2048
    per_layer = 3 * 2 * expert * 16384 / 197e12
    assert per_layer > (8 * expert * 8 + 5 * 16384 * 2048 * 2) / 819e9
    assert a.moe_gmm_train_roofline_s(s, ROWS, SEQ, KIND) == pytest.approx(
        4 * per_layer)
    assert a.held_params(s) * 12 == pytest.approx(5.63e9, rel=1e-3)


def made_up_trace(listed: int, period: float = 0.55, window: float = 4.0):
    red = xplane.Reduced()
    red.window_s = window
    steps = window / period
    red.devices.append({
        "name": "/device:TPU:0", "ops": {}, "busy_s": window,
        "op_events": [("tpu_custom_call:flash_fwd_bf16_4_8192_2048_",
                       "%flash_fwd.1", 0.1, 0.02 * steps),
                      ("tpu_custom_call:flash_bwd_bf16_4_2048_2048_",
                       "%flash_bwd.1", 0.5, 0.03 * steps),
                      ("tpu_custom_call:moe_gmm_fwd_bf16_", "%moe_gmm.2",
                       1.5, 0.03 * steps)],
        "modules": [("jit_step(1)", 0.05 + period * i, period * 0.99)
                    for i in range(listed)]})
    return red


def a_run(cell, red, index=None):
    run = {"trace": red, "cell": cell, "sizes": cell.sizes(), "rows": ROWS,
           "seq": SEQ, "chips": 1, "device_kind": KIND,
           "compile_in_window": 0, "memory_peak_bytes": 1,
           "span_snapshot": type("S", (), {"spans": []})()}
    if index is not None:
        run["scope_index"] = index
    return run


def test_the_rooflines_from_a_made_up_trace(cell):
    """Whether the trace lists seven executions of the step or eight."""
    for n in (7, 8):
        g = cell.read_per_layer(a_run(cell, made_up_trace(n), {}))
        assert g["full_attn_roofline.train"]["value"] == pytest.approx(
            100 * PINNED["flash_train_roofline_s"][1] / 0.05)
        assert g["moe_gmm_roofline.train"]["value"] == pytest.approx(
            100 * PINNED["moe_gmm_train_roofline_s"][1] / 0.03)
        assert g["flash_fwd_share.train"]["value"] == pytest.approx(
            100 * 0.02 / 0.55)
        assert g["train_step_mfu.train"]["value"] == pytest.approx(
            100 * 39892058898432.0 / (0.55 * 197e12))


def op(text, path=None):
    return scopes.parse_op(text, path or "")[1]


LAYER = "jit(step)/transpose(jvp(layers))/while/body/closed_call/"
INDEX = {
    # the leading layer's mixer, forward: its projection, its gates and
    # convolution
    "dot.1": op("%dot.1 = bf16[4,8192,6144]{2,1,0} dot(bf16[4,8192,2048]"
                "{2,1,0} %p.1, bf16[2048,6144]{1,0} %p.2)",
                "jit(step)/jvp(lead)/shortconv/dot_general"),
    "fusion.2": op("%fusion.2 = bf16[4,8192,2048]{2,1,0} fusion(bf16[4,8192,"
                   "6144]{2,1,0} %dot.1), kind=kLoop",
                   "jit(step)/jvp(lead)/shortconv/shortconv.conv/mul"),
    # a scanned layer's, backward
    "fusion.3": op("%fusion.3 = bf16[4,8192,2048]{2,1,0} fusion(bf16[4,8192,"
                   "2048]{2,1,0} %p.3), kind=kLoop",
                   LAYER + "shortconv/shortconv.conv/mul"),
    # XLA's copy of the gates' result: it borrows their path
    "copy.4": op("%copy.4 = bf16[4,8192,2048]{1,2,0} copy(bf16[4,8192,2048]"
                 "{2,1,0} %fusion.3)"),
    "fusion.5": op("%fusion.5 = bf16[4,8192,2048]{2,1,0} fusion(bf16[4,8192,"
                   "2048]{2,1,0} %p.4), kind=kLoop", LAYER + "attn/mul"),
}
EVENTS = [("fusion_a", "%dot.1 = bf16[4,8192,6144]{2,1,0} dot(", 0.0, 0.4),
          ("fusion_b", "%fusion.2 = bf16[4,8192,2048]{2,1,0} fusion(", 0.5,
           0.1),
          ("fusion_c", "%fusion.3 = bf16[4,8192,2048]{2,1,0} fusion(", 1.0,
           0.2),
          ("copy_d", "%copy.4 = bf16[4,8192,2048]{1,2,0} copy(", 1.5, 0.1),
          ("fusion_e", "%fusion.5 = bf16[4,8192,2048]{2,1,0} fusion(", 2.0,
           1.0)]


def scoped_trace(events):
    red = xplane.Reduced()
    red.window_s = 4.0
    red.devices.append({"name": "/device:TPU:0", "ops": {}, "busy_s": 2.0,
                        "op_events": list(events), "modules": []})
    return red


def test_the_new_metrics_find_the_mixer_anywhere_in_the_path(cell):
    """`shortconv` and `shortconv.conv` are outside the scope vocabulary:
    the scope table counts them under `lead` and `layers`, and `path_share`
    finds them in the path whatever encloses them, XLA's copy of the gates'
    result by the path it borrows."""
    run = a_run(cell, scoped_trace(EVENTS), INDEX)
    got = cell.read_per_layer(run)
    assert got["shortconv_share.train"]["value"] == pytest.approx(
        100 * (0.4 + 0.1 + 0.2 + 0.1) / 2.0)
    assert got["shortconv_conv_share.train"]["value"] == pytest.approx(
        100 * (0.1 + 0.2 + 0.1) / 2.0)
    cells_ = run["scope_table"].by_pass_and_scope()
    assert cells_[("forward", "lead")] == pytest.approx(0.5)
    assert cells_[("backward", "layers")] == pytest.approx(0.3)
    assert "shortconv" not in scopes.VOCAB


def test_the_metrics_read_nothing_where_nothing_is(cell):
    """A program without the mixer, the kernels or the marks (the
    parent's): the metrics are left out of the line, nothing raises."""
    index = {"fusion.5": INDEX["fusion.5"]}
    red = scoped_trace(EVENTS[-1:])
    got = cell.read_per_layer(a_run(cell, red, index))
    assert not {"shortconv_share.train", "shortconv_conv_share.train",
                "full_attn_roofline.train", "moe_gmm_roofline.train",
                "moe_experts_share.train",
                "moe_dispatch_rows_over_live.train"} & set(got)
    # and with no trace and no marks at all
    assert cell.read_per_layer({
        "compile_in_window": 0, "memory_peak_bytes": 1,
        "span_snapshot": type("S", (), {"spans": []})()}).keys() == {
        "compile_in_window", "device_peak_hbm_bytes.train"}


def test_the_cell_reports_what_the_benchmark_asks_of_it(cell):
    assert {m["name"] for m in cell.end_to_end()} == {"train_tokens_per_s",
                                                      "setup_s"}
    assert {m["name"] for m in cell.per_layer()} == {
        "compile_in_window", "train_step_mfu.train",
        "device_idle_share.train", "device_peak_hbm_bytes.train",
        "flash_fwd_calls_per_step.train", "flash_fwd_share.train",
        "flash_bwd_share.train", "layout_copy_share.train",
        "full_attn_roofline.train", "moe_experts_share.train",
        "moe_gmm_roofline.train", "moe_dispatch_rows_over_live.train",
        "moe_overflow_share.train", "unscoped_share.train",
        "recompute_share.train", "optimizer_share.train",
        "head_loss_share.train", "shortconv_share.train",
        "shortconv_conv_share.train"}
    assert cell.chips == 1
    for name, arg in (("shortconv_share.train", "shortconv"),
                      ("shortconv_conv_share.train", "shortconv.conv")):
        new = next(m for m in cell.benchmark["per_layer"]
                   if m["name"] == name)
        assert new == {"name": name, "unit": "%", "better": "lower",
                       "source": "device_trace", "layer": "training step",
                       "moves": "train_tokens_per_s", "workloads": [CELL]}
        spec = json.loads((ROOT / "metrics" / f"{name}.json").read_text())
        assert spec == {"reader": "path_share", "args": {"name": arg}}


def test_the_cell_rehearses_on_the_cpu():
    out = subprocess.run(
        ["python3", str(ROOT / "run.py"), "--workload", CELL, "--seed",
         str(2 ** 31 + 13), "--seconds", "2", "--trace", "0", "--rehearse"],
        capture_output=True, text=True, timeout=900, cwd=ROOT.parent)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    assert line["failed"] == 0 and line["attempted"] > 3


def test_the_configuration_keeps_every_published_number(cell):
    """Every key of the catalog row's `config` as published, but the five
    keys in `reduced`; the nested rotary group whole."""
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
        "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
        "num_hidden_layers": 40, "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 65536,
        "layer_types": ["conv", "conv"] + ["full_attention", "conv", "conv",
                                           "conv"] * 9 + ["full_attention",
                                                          "conv"]}
    cfg = cell.config
    differs = sorted(k for k, v in published.items() if cfg.get(k) != v)
    assert differs == sorted(cfg["reduced"]) == [
        "layer_types", "num_dense_layers", "num_experts",
        "num_hidden_layers", "vocab_size"]
    assert cfg["published"] == {k: published[k] for k in cfg["reduced"]}
    assert cfg["router_width"] == published["num_experts"]
    assert 8 * cfg["vocab_size"] == published["vocab_size"]
    assert 8 * cfg["num_experts"] == published["num_experts"]
    # one of the two alike leading layers, and the published layers 2 to 5
    assert cfg["layer_types"] == published["layer_types"][1:6]
    assert cfg["num_hidden_layers"] - cfg["num_dense_layers"] == 4
    assert cfg["tie_embedding"] is True and cfg["router_trained"] is False
    entry = next(c for c in cell.benchmark["configs"]
                 if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]


def test_what_the_benchmark_had_is_untouched():
    """Every file of perfbench/ that the parent commit has, byte for byte;
    BENCHMARK.json's old entries as they were, this PR's after them (and
    whatever later PRs add after those)."""
    def git(*args):
        return subprocess.run(["git", *args], cwd=ROOT.parent,
                              capture_output=True, text=True)
    if git("cat-file", "-e", PARENT).returncode:
        pytest.skip("the parent commit is not in this checkout")
    for line in git("ls-tree", "-r", PARENT,
                    "perfbench").stdout.splitlines():
        meta, path = line.split("\t")
        blob = meta.split()[2]
        data = (ROOT.parent / path).read_bytes()
        head = f"blob {len(data)}\0".encode()
        assert hashlib.sha1(head + data).hexdigest() == blob, path
    old = json.loads(git("show", f"{PARENT}:BENCHMARK.json").stdout)
    new = json.loads((ROOT.parent / "BENCHMARK.json").read_text())
    for key in ("command", "paths", "run_seconds"):
        assert new[key] == old[key]
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for was, now in zip(old[key], new[key]):
            lists = now.get("workloads", [])[:len(was.get("workloads", []))]
            assert dict(now, workloads=lists) == dict(
                was, workloads=was.get("workloads", []))
    assert new["workloads"][len(old["workloads"])]["name"] == CELL
    assert new["configs"][len(old["configs"])]["name"] == "lfm2-24b-a2b"
    assert [m["name"] for m in new["per_layer"][len(old["per_layer"]):][:2]] \
        == ["shortconv_share.train", "shortconv_conv_share.train"]
