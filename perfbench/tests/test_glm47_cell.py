"""The GLM-4.7-Flash configuration's files: its counts pinned and worked by
hand, its metrics on a made-up reduced trace and a made-up mark, the cell's
rehearsal, the configuration against the catalog's row, and that what the
benchmark had is untouched.

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
CELL = "glm47flash-train-ep8share-1chip"
KIND = "TPU v5 lite"
ROWS, SEQ = 2, 8192
PARENT = "bb0bd3e30c78b3aa19bd59d42474293b0b74d952"

PINNED = {
    "held_params": ((), 706518848),
    "matmul_params": ((), 268500992.0),
    "mtp_matmul_params": ((), 84082688.0),
    "train_flops_per_step": ((ROWS, SEQ), 59400401977344.0),
    "flash_train_roofline_s": ((ROWS, SEQ, KIND), 0.1255889588369543),
    "moe_gmm_train_roofline_s": ((ROWS, SEQ, KIND), 0.011772719374294416),
    "moe_live_rows": ((ROWS * SEQ,), 8192.0),
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
    """ISSUE 36's parameters, none tuned to the readings: Adam at the other
    typed cells' 3e-4, two packed rows of 8192, a block of eight, the dense
    loss, full remat a layer, a 4 s trace."""
    tr = cell.traffic
    assert (tr["entry"], tr["learning_rate"], tr["rows"], tr["seq"],
            tr["block"], tr["xent_chunk"], tr["remat"], tr["remat_policy"],
            tr["trace_s"], tr["eot_id"]) == (
        "megatron", 3e-4, 2, 8192, 8, 0, True, "full", 4, 19359)
    assert tr["doc_len"] == {"dist": "lognormal", "median": 1000,
                             "sigma": 1.0, "min": 16, "max": 8192}


def test_the_counts_by_hand(cell):
    a, s = cell.arch(), cell.sizes()
    assert (cell.traffic["rows"], cell.traffic["seq"]) == (ROWS, SEQ)
    assert (s.n_expert_layers, s.qk_head_dim, s.experts_held,
            s.n_routed_experts) == (4, 256, 8, 64)
    # the mixer: q_a, q_b, kv_a (latent and the one rotary key), kv_b
    # (a head's k_nope and v), o; the two latents' norms beside them
    matrices = (2048 * 768 + 768 * 20 * 256 + 2048 * (512 + 64)
                + 512 * 20 * (192 + 256) + 20 * 256 * 2048)
    mixer = matrices + 768 + 512
    assert (matrices, mixer) == (21757952, 21759232)
    dense_mlp, expert = 3 * 2048 * 10240, 3 * 2048 * 1536
    dense = mixer + 2 * 2048 + dense_mlp
    layer = mixer + 2 * 2048 + 2048 * 64 + 64 + expert + 8 * expert
    ends = 2 * 19360 * 2048 + 2048
    mtp = 3 * 2048 + 4096 * 2048 + layer
    assert (dense, layer, ends, mtp) == (84677888, 106829120, 79300608,
                                         115223872)
    assert a.held_params(s) == dense + 4 * layer + ends + mtp == 706518848
    assert 706518848 * 12 == pytest.approx(8.48e9, rel=1e-3)
    # a token meets the matrices (not the norms, not the bias, which is
    # added), the shared expert, and 4 x 8 / 64 = half an expert
    met = matrices + 2048 * 64 + expert + expert // 2
    assert met == 36044800
    main = (matrices + dense_mlp) + 4 * met + 2048 * 19360
    module = 4096 * 2048 + met + 2048 * 19360
    assert a.matmul_params(s) == main == 268500992
    assert a.mtp_matmul_params(s) == module == 84082688
    # attention: six layers, the module's over T - 1 positions a row
    pairs = ROWS * (5 * arith.causal_pairs(SEQ)
                    + arith.causal_pairs(SEQ - 1))
    assert pairs == 402685952
    attn = 2 * 20 * (256 + 256) * pairs
    n, n1 = ROWS * SEQ, ROWS * (SEQ - 1)
    assert a.train_flops_per_step(s, ROWS, SEQ) == pytest.approx(
        3 * (2 * main * n + 2 * module * n1 + attn))
    assert 3 * 2 * (main * n + module * n1) == pytest.approx(34.66e12,
                                                             rel=1e-3)
    assert 3 * attn == pytest.approx(24.74e12, rel=1e-3)
    # ISSUE 36's 83.9 MFLOP a token forward, on average over a row
    assert 2 * 20 * 512 * arith.causal_pairs(SEQ) / SEQ == pytest.approx(
        83.9e6, rel=1e-3)
    # attention is FLOP-bound: q, k 20 x 256 wide, v, o 20 x 256
    assert a.flash_train_roofline_s(s, ROWS, SEQ, KIND) == pytest.approx(
        3 * attn / 197e12)
    nbytes = 6 * 20 * (256 + 256) * (5 * n + n1) * 2
    assert 3 * attn / 197e12 > nbytes / 819e9
    # the routed experts: 8 x 1,024 live rows a layer, FLOP-bound
    assert a.moe_live_rows(s, n) == 8 * 1024
    per_layer = 3 * 2 * expert * 8192 / 197e12
    assert per_layer > (8 * expert * 8 + 5 * 8192 * 2048 * 2) / 819e9
    assert a.moe_gmm_train_roofline_s(s, ROWS, SEQ, KIND) == pytest.approx(
        4 * per_layer + 3 * 2 * expert * 8191 / 197e12)


def made_up_trace(listed: int, period: float = 0.9, window: float = 4.0):
    red = xplane.Reduced()
    red.window_s = window
    steps = window / period
    red.devices.append({
        "name": "/device:TPU:0", "ops": {}, "busy_s": window,
        "op_events": [("tpu_custom_call:flash_fwd_bf16_40_8192_256_",
                       "%flash_fwd.1", 0.1, 0.16 * steps),
                      ("tpu_custom_call:flash_bwd_bf16_40_8192_256_",
                       "%flash_bwd.1", 0.5, 0.14 * steps),
                      ("tpu_custom_call:moe_gmm_fwd_bf16_", "%moe_gmm.2",
                       1.5, 0.03 * steps)],
        "modules": [("jit_step(1)", 0.05 + period * i, period * 0.99)
                    for i in range(listed)]})
    return red


def a_mark(name, **args):
    m = type("Span", (), {})()
    m.name, m.args = name, args
    return m


def a_run(cell, red, spans=()):
    return {"trace": red, "cell": cell, "sizes": cell.sizes(), "rows": ROWS,
            "seq": SEQ, "chips": 1, "device_kind": KIND,
            "compile_in_window": 0, "memory_peak_bytes": 1,
            "span_snapshot": type("S", (), {"spans": list(spans)})()}


def test_the_rooflines_from_a_made_up_trace(cell):
    """Whether the trace lists four executions of the step or five."""
    got = [cell.read_per_layer(a_run(cell, made_up_trace(n)))
           for n in (4, 5)]
    for g in got:
        assert g["full_attn_roofline.train"]["value"] == pytest.approx(
            100 * PINNED["flash_train_roofline_s"][1] / 0.30)
        # not on the list: the count is a balanced router's, the cell's
        # load is not (PERF.md, Findings, PR 36)
        assert "moe_gmm_roofline.train" not in g
        assert g["full_attn_roofline.train"]["value"] < 100
        assert g["flash_fwd_share.train"]["value"] == pytest.approx(
            100 * 0.16 / 0.9)
        assert g["train_step_mfu.train"]["value"] == pytest.approx(
            100 * 59400401977344.0 / (0.9 * 197e12))


def test_the_ratios_from_the_marks(cell):
    marks = [a_mark("mla.layout", form="expanded", heads=20, qk_dim=256,
                    v_dim=256, kv_rank=512, rope_dim=64,
                    kv_expanded_elems=10240, kv_latent_elems=576),
             a_mark("moe.share", held=8, of=64, top_k=4, tokens=16384,
                    buffer_rows=18688, scoring="sigmoid")]
    run = {"span_snapshot": type("S", (), {"spans": marks})(),
           "compile_in_window": 0, "memory_peak_bytes": 1}
    got = cell.read_per_layer(run)
    assert got["mla_kv_expanded_over_latent.train"]["value"] == \
        pytest.approx(10240 / 576) == pytest.approx(17.78, abs=0.005)
    assert got["moe_dispatch_rows_over_live.train"]["value"] == \
        pytest.approx(18688 / 8192) == 2.28125


def test_the_metrics_read_nothing_where_nothing_is(cell):
    """A program without the kernels or the marks (the parent's): the
    metrics are left out of the line, nothing raises."""
    red = made_up_trace(4)
    red.devices[0]["op_events"] = []
    got = cell.read_per_layer(a_run(cell, red))
    assert not {"mla_kv_expanded_over_latent.train",
                "moe_dispatch_rows_over_live.train",
                "full_attn_roofline.train", "moe_gmm_roofline.train",
                "moe_experts_share.train"} & set(got)


def test_the_cell_reports_what_the_benchmark_asks_of_it(cell):
    assert {m["name"] for m in cell.end_to_end()} == {"train_tokens_per_s",
                                                      "setup_s"}
    assert {m["name"] for m in cell.per_layer()} == {
        "compile_in_window", "train_step_mfu.train",
        "device_idle_share.train", "device_peak_hbm_bytes.train",
        "flash_fwd_calls_per_step.train", "flash_fwd_share.train",
        "flash_bwd_share.train", "layout_copy_share.train",
        "full_attn_roofline.train", "moe_experts_share.train",
        "moe_dispatch_rows_over_live.train",
        "mla_kv_expanded_over_latent.train"}
    assert cell.chips == 1
    new = next(m for m in cell.benchmark["per_layer"]
               if m["name"] == "mla_kv_expanded_over_latent.train")
    assert new == {"name": "mla_kv_expanded_over_latent.train",
                   "unit": "ratio", "better": "lower",
                   "source": "program_counter", "layer": "training step",
                   "moves": "train_tokens_per_s", "workloads": [CELL]}


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
    the three keys in `reduced`."""
    published = {
        "hidden_size": 2048, "intermediate_size": 10240,
        "max_position_embeddings": 202752, "moe_intermediate_size": 1536,
        "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
        "n_routed_experts": 64, "n_shared_experts": 1,
        "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
        "first_k_dense_replace": 1, "num_hidden_layers": 47,
        "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
        "partial_rotary_factor": 1, "rms_norm_eps": 1e-05,
        "rope_theta": 1000000, "q_lora_rank": 768, "kv_lora_rank": 512,
        "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256,
        "vocab_size": 154880}
    cfg = cell.config
    differs = sorted(k for k, v in published.items() if cfg.get(k) != v)
    assert differs == sorted(cfg["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert cfg["router_width"] == published["n_routed_experts"]
    assert cfg["published"] == {k: published[k] for k in cfg["reduced"]}
    assert 8 * cfg["vocab_size"] == published["vocab_size"]
    assert 8 * cfg["n_routed_experts"] == published["n_routed_experts"]
    # the leading dense layer and four of the layers that follow it
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] == 4
    for key, want in (("attention_bias", False), ("hidden_act", "silu"),
                      ("model_type", "glm4_moe_lite"),
                      ("topk_method", "noaux_tc"), ("norm_topk_prob", True),
                      ("rope_scaling", None),
                      ("tie_word_embeddings", False)):
        assert cfg[key] == want, key
    assert cfg["mtp_loss_weight"] == 0.3 and isinstance(cfg["weights_key"],
                                                        int)
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
    assert new["configs"][len(old["configs"])]["name"] == "glm-4.7-flash"
    assert new["per_layer"][len(old["per_layer"])]["name"] == \
        "mla_kv_expanded_over_latent.train"
