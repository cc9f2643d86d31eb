"""Cross-layer LSTM wavefront fusion == the sequential per-layer scans
(nn/layers/recurrent.wavefront_scan_stack; measured 1.14-1.28x on chip
on an earlier toolchain, BASELINE.md r4). Exactness is the scan-everything
house rule's proof obligation: same cell math, same states, same final
carries, through the full MultiLayerNetwork surface."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import (GravesLSTM,
                                          GravesBidirectionalLSTM,
                                          RnnOutputLayer)
from deeplearning4j_tpu.nn.layers.recurrent import (
    wavefront_eligible_run, wavefront_scan_stack)


def _mk_net(seed=3, layers=2, dropout=0.0):
    ls = [GravesLSTM(n_in=5 if i == 0 else 12, n_out=12,
                     activation="tanh",
                     dropout=dropout if i > 0 else 0.0)
          for i in range(layers)]
    conf = (NeuralNetConfiguration(seed=seed, updater="sgd",
                                   learning_rate=0.1)
            .list(*ls, RnnOutputLayer(n_in=12, n_out=4,
                                      activation="softmax",
                                      loss_function="mcxent")))
    return MultiLayerNetwork(conf).init()


def test_stack_matches_sequential_scans_and_carries():
    """Direct check at n=3 (deeper than the benchmarked pair):
    outputs AND per-layer final carries equal the chained
    scan_sequence path."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 9, 5)), jnp.float32)
    layers = [GravesLSTM(n_in=5, n_out=8, activation="tanh"),
              GravesLSTM(n_in=8, n_out=8, activation="tanh"),
              GravesLSTM(n_in=8, n_out=8, activation="tanh")]
    plist = [l.init_params(jax.random.PRNGKey(i)) for i, l in
             enumerate(layers)]
    ys, finals = wavefront_scan_stack(layers, plist, x)
    h = x
    for l, p, fc in zip(layers, plist, finals):
        h, carry = l.scan_sequence(p, h)
        np.testing.assert_allclose(np.asarray(carry[0]),
                                   np.asarray(fc[0]), rtol=1e-5,
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(carry[1]),
                                   np.asarray(fc[1]), rtol=1e-5,
                                   atol=1e-6)
    np.testing.assert_allclose(np.asarray(ys), np.asarray(h),
                               rtol=1e-5, atol=1e-6)


def test_mln_output_and_training_match_with_fusion_off(monkeypatch):
    """The full MLN surface: inference output and one fit_batched
    epoch (i.e. gradients) are equal with the wavefront disabled vs
    enabled."""
    rng = np.random.default_rng(1)
    x = rng.normal(size=(8, 7, 5)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, (8, 7))]

    monkeypatch.setenv("DL4JTPU_WAVEFRONT", "0")
    net_off = _mk_net()
    out_off = np.asarray(net_off.output(x))
    s_off = np.asarray(net_off.fit_batched(x[None], y[None], epochs=3))
    p_off = jax.tree_util.tree_leaves(net_off.params)

    monkeypatch.delenv("DL4JTPU_WAVEFRONT")
    net_on = _mk_net()
    out_on = np.asarray(net_on.output(x))
    s_on = np.asarray(net_on.fit_batched(x[None], y[None], epochs=3))
    p_on = jax.tree_util.tree_leaves(net_on.params)

    np.testing.assert_allclose(out_off, out_on, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(s_off, s_on, rtol=1e-5, atol=1e-6)
    for a, b in zip(p_off, p_on):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_tbptt_carry_path_matches(monkeypatch):
    """TBPTT streams (h, c) carries between chunks through the fused
    path — scores must match the unfused run chunk for chunk."""
    from deeplearning4j_tpu.models.zoo import char_rnn_lstm
    rng = np.random.default_rng(2)
    V, B, T = 11, 4, 24
    ids = rng.integers(0, V, (B, T))
    x = np.eye(V, dtype=np.float32)[ids]
    y = np.eye(V, dtype=np.float32)[np.roll(ids, -1, axis=1)]

    def run():
        conf = char_rnn_lstm(vocab_size=V, hidden=10, layers=2,
                             tbptt_length=8, dtype="float32")
        net = MultiLayerNetwork(conf).init()
        net.fit(x, y)
        return (float(net.score_value),
                jax.tree_util.tree_leaves(net.params))

    monkeypatch.setenv("DL4JTPU_WAVEFRONT", "0")
    s_off, p_off = run()
    monkeypatch.delenv("DL4JTPU_WAVEFRONT")
    s_on, p_on = run()
    np.testing.assert_allclose(s_off, s_on, rtol=1e-5)
    for a, b in zip(p_off, p_on):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_eligibility_rules():
    l1 = GravesLSTM(n_in=5, n_out=8)
    l2 = GravesLSTM(n_in=8, n_out=8)
    bi = GravesBidirectionalLSTM(n_in=8, n_out=8)
    names = ["a", "b", "c"]
    assert wavefront_eligible_run(
        [l1, l2, bi], names, 0, train=False, mask=None, carries=None,
        preprocessors={}) == [0, 1]
    # bidirectional breaks the run; a run of one is no run
    assert wavefront_eligible_run(
        [l1, bi, l2], names, 0, train=False, mask=None, carries=None,
        preprocessors={}) == []
    # mask disables
    assert wavefront_eligible_run(
        [l1, l2], names[:2], 0, train=False, mask=jnp.ones((2, 4)),
        carries=None, preprocessors={}) == []
    # train-time dropout on the SECOND layer breaks fusion
    l2d = GravesLSTM(n_in=8, n_out=8, dropout=0.5)
    assert wavefront_eligible_run(
        [l1, l2d], names[:2], 0, train=True, mask=None, carries=None,
        preprocessors={}) == []
    assert wavefront_eligible_run(
        [l1, l2d], names[:2], 0, train=False, mask=None, carries=None,
        preprocessors={}) == [0, 1]
    # partial carries coverage disables (all-or-nothing)
    assert wavefront_eligible_run(
        [l1, l2], names[:2], 0, train=False, mask=None,
        carries={"a": 1}, preprocessors={}) == []
    assert wavefront_eligible_run(
        [l1, l2], names[:2], 0, train=False, mask=None,
        carries={"a": 1, "b": 2}, preprocessors={}) == [0, 1]
