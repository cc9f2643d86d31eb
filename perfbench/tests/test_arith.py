"""The FLOP and byte counts against hand-worked values, that no share of a
peak can pass 100% at the cells' shapes, and that the counts of the module a
configuration's file names (`arch`) are what `harness/arith.py` gave before
they moved there."""
import json
from pathlib import Path

import pytest

from perfbench.harness import arith, cells

ROOT = Path(__file__).resolve().parents[1]


def family(name):
    """The arch module that the configuration's file names."""
    cfg = json.loads((ROOT / "configs" / f"{name}.json").read_text())
    return cells.load_module(ROOT / "archs" / f"{cfg['arch']}.py",
                             f"perfbench_arch_{cfg['arch']}"), cfg


def sizes(name):
    mod, cfg = family(name)
    return mod.sizes(cfg)


arch = family("gpt2-medium")[0]

KIND = "TPU v5 lite"
# what harness/arith.py returned at PR 27, before the counts moved
PINNED = {
    "gpt2-medium": {
        "matmul_params": ((), 353453056),
        "held_params": ((), 406188032),
        "train_flops_per_step": ((32, 1024), 74444332597248.0),
        "flash_train_roofline_s": ((32, 1024, KIND), 0.02514027494010152),
        "decode_attn_roofline_s": ((100000, KIND), 0.012002930402930403)},
    "cerebras-gpt-1.3b": {
        "matmul_params": ((), 1310885888),
        "held_params": ((), 1418452992),
        "train_flops_per_step": ((32, 1024), 267635920994304.0),
        "flash_train_roofline_s": ((32, 1024, KIND), 0.05028054988020304),
        "decode_attn_roofline_s": ((100000, KIND), 0.024005860805860806)}}


@pytest.mark.parametrize("config,count", [
    (c, n) for c, counts in PINNED.items() for n in counts])
def test_the_counts_are_what_they_were_before_the_move(config, count):
    """To the last bit: `==` on the floats, no tolerance."""
    mod, cfg = family(config)
    args, want = PINNED[config][count]
    got = getattr(mod, count)(mod.sizes(cfg), *args)
    assert got == want and type(got) is type(want)


def test_parameter_counts_by_hand():
    g = sizes("gpt2-medium")
    # 24 * (4 * 1024^2 + 2 * 1024 * 4096) + 1024 * 50257
    assert arch.matmul_params(g) == 24 * 12582912 + 51463168 == 353453056
    c = sizes("cerebras-gpt-1.3b")
    assert arch.matmul_params(c) == 24 * 50331648 + 102926336 == 1310885888
    # held: + embedding, positions, MLP biases, norms (separate head kept)
    assert arch.held_params(g) == 353453056 + 51463168 + 1048576 \
        + 24 * (4096 + 1024 + 4 * 1024) + 2 * 1024 == 406188032
    assert arch.held_params(c) == 1310885888 + 102926336 + 4194304 \
        + 24 * (8192 + 2048 + 4 * 2048) + 2 * 2048 == 1418452992


def test_train_flops_per_token_by_hand():
    g = sizes("gpt2-medium")
    per_token = arch.train_flops_per_step(g, 16, 1024) / (16 * 1024)
    # 6 * 353.45 M + 3 * 4 * 1024 * 24 * (1025 / 2) = 2.1207 G + 0.1511 G
    assert per_token == pytest.approx(6 * 353453056
                                      + 12 * 1024 * 24 * 512.5)
    assert per_token == pytest.approx(2.2719e9, rel=1e-4)
    c = sizes("cerebras-gpt-1.3b")
    per_token = arch.train_flops_per_step(c, 16, 2048) / (16 * 2048)
    assert per_token == pytest.approx(6 * 1310885888
                                      + 12 * 2048 * 24 * 1024.5)
    assert per_token == pytest.approx(8.4696e9, rel=1e-4)


def test_decode_token_by_hand():
    c = sizes("cerebras-gpt-1.3b")
    # one token over a cache of 1000 rows: 2 * 1.3109 G + 4 * 2048 * 24 * 1000
    assert arch.forward_flops(c, 1, 1000) == pytest.approx(
        2 * 1310885888 + 196608000)
    # its attention reads 1000 K and 1000 V rows of 2048 bf16, 24 layers
    t = arch.decode_attn_roofline_s(c, 1000, "TPU v5 lite")
    assert t == pytest.approx(2 * 1000 * 2048 * 2 * 24 / 819e9)


def test_flash_roofline_by_hand():
    g = sizes("gpt2-medium")
    t = arch.flash_train_roofline_s(g, 16, 1024, "TPU v5 lite")
    pairs = 16 * (1024 * 1025 // 2) * 24
    assert t == pytest.approx(12 * 1024 * pairs / 197e12)     # FLOP-bound
    assert 12 * 1024 * pairs / 197e12 > \
        12 * 16 * 1024 * 1024 * 2 * 24 / 819e9


def test_span_pairs():
    assert arith.causal_pairs(4) == 10
    assert arith.span_pairs(100, 3) == 101 + 102 + 103


def test_an_unknown_device_is_an_error():
    with pytest.raises(SystemExit):
        arith.peaks("TPU v9 imaginary")
    assert arith.peaks("TPU v5 lite") == {"flops_per_s": 197e12,
                                          "bytes_per_s": 819e9}


@pytest.mark.parametrize("config,rows,seq", [("gpt2-medium", 32, 1024),
                                             ("cerebras-gpt-1.3b", 4, 2048)])
def test_no_share_can_pass_100_percent(config, rows, seq):
    """The counted work never exceeds what the implementation must do, so a
    share reads over 100% only if the chip beat its published peak: the
    step's required FLOPs are under what XLA executes (which adds the
    recomputation, the softmax, the norms), and the kernels' counted FLOPs
    are the live half of what a blocked kernel multiplies."""
    s = sizes(config)
    step = arch.train_flops_per_step(s, rows, seq)
    dense_attn = 3 * 4.0 * s.n_embd * s.n_layer * rows * seq * seq
    causal = 3 * 4.0 * s.n_embd * s.n_layer * rows * arith.causal_pairs(seq)
    assert causal < dense_attn
    assert step == pytest.approx(6.0 * arch.matmul_params(s) * rows * seq
                                 + causal)
    # decode attention: counted bytes are exactly the live rows, which any
    # implementation has to read at least once
    assert arch.decode_attn_roofline_s(s, 1, "TPU v5 lite") * 819e9 == \
        pytest.approx(2 * s.n_embd * 2 * s.n_layer)
