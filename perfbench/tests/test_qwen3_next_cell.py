"""The Qwen3-Next configuration's files: its counts pinned and worked by
hand, its reader of a kernel's roofline share on a made-up reduced trace,
the cell's rehearsal, and that what the benchmark had is untouched.

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
CELL = "qwen3next-train-ep16share-1chip"
KIND = "TPU v5 lite"
ROWS = 3            # the traffic file's: 3 x 8192 = 24,576 tokens a step

PINNED = {
    "held_params": ((), 625667136),
    "matmul_params": ((), 191963136.0),
    "train_flops_per_step": ((ROWS, 8192), 34066271305728.0),
    "flash_train_roofline_s": ((ROWS, 8192, KIND), 0.0251188137297868),
    "gdn_train_roofline_s": ((ROWS, 8192, KIND), 0.0059688172307692305),
    "moe_gmm_train_roofline_s": ((ROWS, 8192, KIND),
                                 0.005886503400609137),
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
    assert cell.traffic["rows"] == ROWS and cell.traffic["seq"] == 8192
    gdn = 2048 * 12288 + 2048 * 64 + 8192 * 4 + 32 + 32 + 128 + 4096 * 2048
    full = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 256
    moe_out = 2048 * 512 + 3 * 2048 * 512 + 2048
    expert = 3 * 2048 * 512
    assert (gdn, full, moe_out) == (33718464, 27263488, 4196352)
    layer_gdn = gdn + moe_out + 32 * expert + 2 * 2048
    layer_full = full + moe_out + 32 * expert + 2 * 2048
    assert (layer_gdn, layer_full) == (138582208, 132127232)
    assert a.held_params(s) == 3 * layer_gdn + layer_full \
        + 2 * 18992 * 2048 + 2048 == 625667136
    # a token meets the matrices (not the norms, A_log, dt_bias), the routed
    # experts at 10 x 32 / 512 of one, and the head
    met = (3 * (gdn - 192) + (full - 512)
           + 4 * (moe_out + 10 * 32 / 512 * expert) + 2048 * 18992)
    assert a.matmul_params(s) == met == 191963136
    n = ROWS * 8192
    pairs = ROWS * arith.causal_pairs(8192)
    delta = 7 * 128 * 128 * 32 * n * 3
    assert a.train_flops_per_step(s, ROWS, 8192) == pytest.approx(
        3 * (2 * met * n + 4 * 4096 * pairs + delta))
    assert a.moe_live_rows(s, n) == 480 * 32 == 15360
    # attention: FLOP-bound; q and o 4096 wide, k and v 512
    assert a.flash_train_roofline_s(s, ROWS, 8192, KIND) == pytest.approx(
        12 * 4096 * pairs / 197e12)
    assert 12 * 4096 * pairs / 197e12 > 6 * (4096 + 512) * n * 2 / 819e9
    # the delta rule: bound by its bytes (q, k 2048 wide, v, o 4096, the
    # gates float32 a value head), not by its 0.54 TFLOP
    nbytes = ((2 * 2048 + 2 * 4096) * 2 + (2 * 2048 + 4096) + 6 * 32 * 2) \
        * 2 * n * 3
    assert a.gdn_train_roofline_s(s, ROWS, 8192, KIND) == pytest.approx(
        nbytes / 819e9)
    assert nbytes / 819e9 > 3 * delta / 197e12
    # the routed experts: their weights twice in bfloat16 and once in
    # float32, five passes over the live rows; four layers
    wbytes = 32 * expert * 8 + 5 * 15360 * 2048 * 2
    assert a.moe_gmm_train_roofline_s(s, ROWS, 8192, KIND) == pytest.approx(
        4 * max(6 * expert * 15360 / 197e12, wbytes / 819e9))


def made_up_trace(listed: int, period: float = 0.8, window: float = 4.0):
    """A window of `window / period` steps in which the trace lists `listed`
    whole executions of the step; the kernels' seconds are those of the
    whole window either way."""
    red = xplane.Reduced()
    red.window_s = window
    steps = window / period
    red.devices.append({
        "name": "/device:TPU:0", "ops": {}, "busy_s": window,
        "op_events": [("tpu_custom_call:gdn_fwd_bf16_3_8192_4096_",
                       "%gdn_fwd.1", 0.1, 0.05 * steps),
                      ("tpu_custom_call:gdn_bwd_bf16_3_8192_4096_",
                       "%gdn_bwd.1", 0.5, 0.15 * steps),
                      ("tpu_custom_call:moe_gmm_fwd_bf16_", "%moe_gmm_fwd.3",
                       1.0, 0.02 * steps),
                      ("tpu_custom_call:flash_fwd_bf16_", "%flash_fwd.2",
                       1.5, 0.07 * steps)],
        "modules": [("jit_step(1)", 0.05 + period * i, period * 0.99)
                    for i in range(listed)]})
    return red


@pytest.mark.parametrize("metric,count,per_step", [
    ("gdn_scan_roofline.train", "gdn_train_roofline_s", 0.20),
    ("moe_gmm_roofline.train", "moe_gmm_train_roofline_s", 0.02),
    ("full_attn_roofline.train", "flash_train_roofline_s", 0.07)])
def test_kernel_roofline_reads_the_same_from_four_and_five_executions(
        cell, metric, count, per_step):
    got = []
    for listed in (4, 5):
        run = {"trace": made_up_trace(listed), "cell": cell,
               "sizes": cell.sizes(), "rows": ROWS, "seq": 8192, "chips": 1,
               "device_kind": KIND, "compile_in_window": 0,
               "memory_peak_bytes": 1,
               "span_snapshot": type("S", (), {"spans": []})()}
        got.append(cell.read_per_layer(run)[metric]["value"])
    least = PINNED[count][1]
    assert got[0] == pytest.approx(got[1]) == pytest.approx(
        100 * least / per_step)
    assert got[0] < 100


def test_kernel_roofline_and_the_mark_read_nothing_where_nothing_is(cell):
    """A program without the kernels or the mark (the parent's): the
    metrics are left out of the line, nothing raises."""
    red = made_up_trace(4)
    red.devices[0]["op_events"] = []
    run = {"trace": red, "cell": cell, "sizes": cell.sizes(), "rows": ROWS,
           "seq": 8192, "chips": 1, "device_kind": KIND,
           "compile_in_window": 0, "memory_peak_bytes": 1,
           "span_snapshot": type("S", (), {"spans": []})()}
    got = cell.read_per_layer(run)
    assert not {"gdn_scan_roofline.train", "moe_gmm_roofline.train",
                "full_attn_roofline.train", "gdn_scan_share.train",
                "moe_experts_share.train",
                "moe_dispatch_rows_over_live.train"} & set(got)


def test_rows_over_live_from_the_mark(cell):
    span = type("Span", (), {})
    m = span()
    m.name, m.args = "moe.share", dict(held=32, of=512, top_k=10,
                                       tokens=24576, buffer_rows=254208)
    run = {"span_snapshot": type("S", (), {"spans": [m]})(),
           "compile_in_window": 0, "memory_peak_bytes": 1}
    got = cell.read_per_layer(run)
    assert got["moe_dispatch_rows_over_live.train"]["value"] == \
        pytest.approx(254208 / 15360) == 16.55


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
    """Every number of the catalog row's `config` under the same key,
    but the three keys in `reduced`."""
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_size": 2048, "intermediate_size": 5120,
        "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
        "linear_num_key_heads": 16, "linear_num_value_heads": 32,
        "linear_value_head_dim": 128, "max_position_embeddings": 262144,
        "moe_intermediate_size": 512, "num_attention_heads": 16,
        "num_experts": 512, "num_experts_per_tok": 10,
        "num_hidden_layers": 48, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
        "vocab_size": 151936}
    cfg = cell.config
    differs = sorted(k for k, v in published.items() if cfg.get(k) != v)
    assert differs == sorted(cfg["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert cfg["router_width"] == published["num_experts"]
    assert cfg["published"] == {k: published[k] for k in cfg["reduced"]}
    assert 8 * cfg["vocab_size"] == published["vocab_size"]
    assert isinstance(cfg["weights_key"], int)


def test_what_the_benchmark_had_is_untouched():
    """Every file of perfbench/ that the parent commit has, byte for byte;
    BENCHMARK.json's old entries as they were, the new ones last."""
    def git(*args):
        return subprocess.run(["git", *args], cwd=ROOT.parent,
                              capture_output=True, text=True)
    base = "501f97459635ff0f3234b6a6af881039a16f02c1"
    if git("cat-file", "-e", base).returncode:
        pytest.skip("the parent commit is not in this checkout")
    for line in git("ls-tree", "-r", base, "perfbench").stdout.splitlines():
        meta, path = line.split("\t")
        blob = meta.split()[2]
        data = (ROOT.parent / path).read_bytes()
        head = f"blob {len(data)}\0".encode()
        assert hashlib.sha1(head + data).hexdigest() == blob, path
    old = json.loads(git("show", f"{base}:BENCHMARK.json").stdout)
    new = json.loads((ROOT.parent / "BENCHMARK.json").read_text())
    for key in ("command", "paths", "run_seconds"):
        assert new[key] == old[key]
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for was, now in zip(old[key], new[key]):
            lists = now.get("workloads", [])[:len(was.get("workloads", []))]
            assert dict(now, workloads=lists) == dict(
                was, workloads=was.get("workloads", []))
    assert new["workloads"][-1]["name"] == CELL
