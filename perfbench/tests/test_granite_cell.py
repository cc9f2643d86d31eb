"""The Granite 4.0-H configuration's files: its counts pinned and worked by
hand, its two new metrics on a made-up reduced trace, the cell's rehearsal,
the configuration against the catalog's row, and that what the benchmark had
is untouched.

(The counts are pinned here and not as further cases of `test_arith.py`'s
parametrised test: a PR that adds a configuration edits no file the
benchmark has.)"""
import hashlib
import json
import subprocess
from pathlib import Path

import pytest

from perfbench.harness import arith, cells, xplane

ROOT = Path(__file__).resolve().parents[1]
CELL = "granite4h-train-t8192-1chip"
KIND = "TPU v5 lite"
ROWS, SEQ = 1, 8192
PARENT = "9b983402da73b6283ca7646b192c903b90a57e22"

PINNED = {
    "held_params": ((), 772160448),
    "matmul_params": ((), 771883008),
    "train_flops_per_step": ((ROWS, SEQ), 39344148578304.0),
    "flash_train_roofline_s": ((ROWS, SEQ, KIND), 0.004186468954964467),
    "ssd_train_roofline_s": ((ROWS, SEQ, KIND), 0.003894710857142857),
}


@pytest.fixture(scope="module")
def cell():
    return cells.Cell(CELL)


@pytest.mark.parametrize("count", sorted(PINNED))
def test_the_counts_are_pinned(cell, count):
    args, want = PINNED[count]
    got = getattr(cell.arch(), count)(cell.sizes(), *args)
    assert got == want and type(got) is type(want)


def test_the_counts_by_hand(cell):
    a, s = cell.arch(), cell.sizes()
    assert (cell.traffic["rows"], cell.traffic["seq"]) == (ROWS, SEQ)
    assert s.layer_types == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    assert (s.n_periods, s.head_dim) == (1, 64)
    in_proj = 2048 * (4096 + 4352 + 64)
    mixer = in_proj + (4352 * 4 + 4352) + 192 + 4096 + 4096 * 2048
    assert (in_proj, mixer) == (17432576, 25847232)
    mlp = 3 * 2048 * 8192
    mamba_layer = mixer + mlp + 2 * 2048
    attn = (2048 + 512 + 512 + 2048) * 2048
    attn_layer = attn + mlp + 2 * 2048
    assert (mlp, mamba_layer, attn, attn_layer) == (
        50331648, 76182976, 10485760, 60821504)
    period = 9 * mamba_layer + attn_layer
    assert period == 746468288
    # the head is the embedding: an eighth of 100,352 rows, once
    assert 8 * 12544 == 100352 and 12544 * 2048 == 25690112
    assert a.held_params(s) == period + 25690112 + 2048 == 772160448
    # a token meets the matrices (not the taps, the vectors, the norms), the
    # MLPs and the head
    met = 9 * (in_proj + 4096 * 2048 + mlp) + (attn + mlp) + 2048 * 12544
    assert a.matmul_params(s) == met == 771883008
    n = ROWS * SEQ
    pairs = ROWS * arith.causal_pairs(SEQ)
    assert pairs == 33558528
    recur = 5 * 64 * 64 * 128 * n * 9
    assert a.train_flops_per_step(s, ROWS, SEQ) == pytest.approx(
        3 * (2 * met * n + 4 * 2048 * pairs + recur))
    assert 3 * 2 * met * n == pytest.approx(37.94e12, rel=1e-3)
    assert 3 * 4 * 2048 * pairs == pytest.approx(0.825e12, rel=1e-3)
    assert 3 * recur == pytest.approx(0.580e12, rel=1e-3)
    # attention: FLOP-bound; q and o 2048 wide, k and v 512
    assert a.flash_train_roofline_s(s, ROWS, SEQ, KIND) == pytest.approx(
        12 * 2048 * pairs / 197e12)
    assert 12 * 2048 * pairs / 197e12 > 6 * (2048 + 512) * n * 2 / 819e9
    # the scan: bound by its bytes. Forward x, B, C in and y out, backward
    # x, B, C, dy in and dx, dB, dC out, the step float32 a head three times
    nbytes = ((2 * 4096 + 256) + (2 * 4096 + 256) + (4096 + 256)) * 2 \
        + 3 * 4 * 64
    assert nbytes == 43264
    assert a.ssd_train_roofline_s(s, ROWS, SEQ, KIND) == pytest.approx(
        nbytes * n * 9 / 819e9)
    assert nbytes * n * 9 / 819e9 > 3 * recur / 197e12


def made_up_trace(listed: int, period: float = 0.5, window: float = 4.0):
    red = xplane.Reduced()
    red.window_s = window
    steps = window / period
    red.devices.append({
        "name": "/device:TPU:0", "ops": {}, "busy_s": window,
        "op_events": [("tpu_custom_call:ssd_fwd_bf16_1_8192_4096_",
                       "%ssd_fwd.1", 0.1, 0.06 * steps),
                      ("tpu_custom_call:ssd_bwd_bf16_1_8192_4096_",
                       "%ssd_bwd.1", 0.5, 0.09 * steps),
                      ("tpu_custom_call:flash_fwd_bf16_", "%flash_fwd.2",
                       1.5, 0.03 * steps)],
        "modules": [("jit_step(1)", 0.05 + period * i, period * 0.99)
                    for i in range(listed)]})
    return red


def a_run(cell, red):
    return {"trace": red, "cell": cell, "sizes": cell.sizes(), "rows": ROWS,
            "seq": SEQ, "chips": 1, "device_kind": KIND,
            "compile_in_window": 0, "memory_peak_bytes": 1,
            "span_snapshot": type("S", (), {"spans": []})()}


def test_the_scans_share_and_roofline_from_a_made_up_trace(cell):
    got = [cell.read_per_layer(a_run(cell, made_up_trace(n)))
           for n in (7, 8)]
    least = PINNED["ssd_train_roofline_s"][1]
    for g in got:
        assert g["ssd_scan_roofline.train"]["value"] == pytest.approx(
            100 * least / 0.15)
        assert g["ssd_scan_roofline.train"]["value"] < 100
        assert g["ssd_scan_share.train"]["value"] == pytest.approx(
            100 * 0.15 / 0.5)
        assert g["full_attn_roofline.train"]["value"] == pytest.approx(
            100 * PINNED["flash_train_roofline_s"][1] / 0.03)


def test_the_new_metrics_read_nothing_where_nothing_is(cell):
    """A program without the kernels (the parent's): the metrics are left
    out of the line, nothing raises."""
    red = made_up_trace(4)
    red.devices[0]["op_events"] = []
    got = cell.read_per_layer(a_run(cell, red))
    assert not {"ssd_scan_roofline.train", "ssd_scan_share.train",
                "full_attn_roofline.train"} & set(got)


def test_the_cell_reports_what_the_benchmark_asks_of_it(cell):
    assert {m["name"] for m in cell.end_to_end()} == {"train_tokens_per_s",
                                                      "setup_s"}
    assert {m["name"] for m in cell.per_layer()} == {
        "compile_in_window", "train_step_mfu.train",
        "device_idle_share.train", "device_peak_hbm_bytes.train",
        "flash_fwd_calls_per_step.train", "flash_fwd_share.train",
        "flash_bwd_share.train", "layout_copy_share.train",
        "full_attn_roofline.train", "ssd_scan_share.train",
        "ssd_scan_roofline.train"}
    assert cell.chips == 1


def test_the_cell_rehearses_on_the_cpu():
    out = subprocess.run(
        ["python3", str(ROOT / "run.py"), "--workload", CELL, "--seed",
         str(2 ** 31 + 11), "--seconds", "2", "--trace", "0", "--rehearse"],
        capture_output=True, text=True, timeout=900, cwd=ROOT.parent)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    assert line["failed"] == 0 and line["attempted"] > 3


def test_the_configuration_keeps_every_published_number(cell):
    """Every number of the catalog row's `config` under the same key, but
    the keys in `reduced`; the period is the published list's first ten."""
    published = {
        "attention_multiplier": 0.015625, "embedding_multiplier": 12,
        "hidden_size": 2048, "intermediate_size": 8192, "logits_scaling": 8,
        "mamba_chunk_size": 256, "mamba_d_conv": 4, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_n_heads": 64, "max_position_embeddings": 131072,
        "num_attention_heads": 32, "num_experts_per_tok": 0,
        "num_hidden_layers": 40, "num_key_value_heads": 8,
        "num_local_experts": 0, "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_theta": 10000,
        "shared_intermediate_size": 8192, "vocab_size": 100352}
    cfg = cell.config
    differs = sorted(k for k, v in published.items() if cfg.get(k) != v)
    assert differs == ["num_hidden_layers", "vocab_size"]
    assert sorted(cfg["reduced"]) == ["layer_types", "num_hidden_layers",
                                      "vocab_size"]
    assert {k: cfg["published"][k] for k in differs} == {
        k: published[k] for k in differs}
    assert 8 * cfg["vocab_size"] == published["vocab_size"]
    assert 4 * cfg["num_hidden_layers"] == published["num_hidden_layers"]
    assert cfg["layer_types"] == ["mamba"] * 5 + ["attention"] \
        + ["mamba"] * 4
    for key, want in (("tie_word_embeddings", True),
                      ("position_embedding_type", "nope"),
                      ("mamba_conv_bias", True), ("mamba_proj_bias", False),
                      ("attention_bias", False), ("hidden_act", "silu"),
                      ("normalization_function", "rmsnorm"),
                      ("model_type", "granitemoehybrid"),
                      ("rope_scaling", None)):
        assert cfg[key] == want, key
    entry = next(c for c in cell.benchmark["configs"]
                 if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]


def test_what_the_benchmark_had_is_untouched():
    """Every file of perfbench/ that the parent commit has, byte for byte;
    BENCHMARK.json's old entries as they were, the new ones after them."""
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
    assert [m["name"] for m in new["per_layer"][len(old["per_layer"]):]] == [
        "ssd_scan_share.train", "ssd_scan_roofline.train"]
