"""Elastic sharded training suite (ISSUE-18).

Unit half: the ZeRO-1 partitioner / flat-vector codec / deterministic
data cursor the elastic coordinator builds on, plus the elastic-off
guarantee (importing the subsystem changes NOTHING for non-elastic
training — bit-identical params, no new metric families).

`multiproc` half: REAL worker processes (train/elastic_worker.py)
under the membership scenarios the acceptance criteria name, each
asserted BIT-EXACT against `reference_run` — the membership-free
single-process oracle:

- SIGKILL one of three workers mid-run, re-add one → final losses and
  params bit-equal the uninterrupted run, and each worker's measured
  updater footprint is the analytic 1/N shard;
- shrink 3→2 then grow 2→3 → same invariant (resharding is a pure
  function of membership SIZE, never of which worker died);
- a straggler drops to SparkNet-style loose sync (typed `elastic`
  events) and resyncs to strict once caught up — zero lost steps;
- a hung worker exhausts `stale_bound`, is evicted, and the lossy
  resize replays from the published checkpoint — exactness RESTORED,
  bit-equal to the oracle with the surviving membership.

Every blocking wait is hard-bounded and the shared
`helpers.child_killing_watchdog` kills worker processes if a test
wedges, so this suite can never hang tier-1.
"""
import numpy as np
import pytest

from deeplearning4j_tpu.models.transformer import TransformerConfig
from deeplearning4j_tpu.observability.events import FlightRecorder
from deeplearning4j_tpu.observability.export import prometheus_text
from deeplearning4j_tpu.observability.metrics import MetricsRegistry
from deeplearning4j_tpu.parallel.failure import ElasticFaultInjector
from deeplearning4j_tpu.parallel.fsdp import (flatten_tree, unflatten_tree,
                                              zero1_partition)
from deeplearning4j_tpu.train.elastic import (ElasticConfig,
                                              ElasticCoordinator,
                                              data_batch, init_flat_params,
                                              param_template, reference_run)
from helpers import child_killing_watchdog

#: tiny model: the properties under test are membership/determinism,
#: not capacity — worker startup (spawn + jit warmup) dominates anyway
CFG = TransformerConfig(vocab_size=32, d_model=16, n_heads=2, n_layers=1,
                        max_len=16)

#: hard wall for anything that could block on a child process
HARD_TIMEOUT_S = 240.0


def _ecfg(tmp_path, **kw):
    base = dict(checkpoint_dir=str(tmp_path / "ckpt"), num_workers=3,
                microbatches_per_step=6, microbatch_size=2, seq_len=8,
                checkpoint_every=1)
    base.update(kw)
    return ElasticConfig(**base)


# ---------------------------------------------------------------------------
# unit: partitioner / codec / data cursor
# ---------------------------------------------------------------------------

def test_zero1_partition_covers_contiguously():
    for n, k in ((10, 3), (4528, 3), (7, 7), (5, 8), (0, 2), (100, 1)):
        bounds = zero1_partition(n, k)
        assert len(bounds) == k
        assert bounds[0][0] == 0 and bounds[-1][1] == n
        for (lo, hi), (lo2, _) in zip(bounds, bounds[1:]):
            assert hi == lo2 and lo <= hi
        # remainder spreads over the FIRST shards; sizes differ by <= 1
        sizes = [hi - lo for lo, hi in bounds]
        assert max(sizes) - min(sizes) <= 1
        assert sorted(sizes, reverse=True) == sizes
    # deterministic: same inputs, same cuts (the resharding contract)
    assert zero1_partition(4528, 3) == zero1_partition(4528, 3)
    with pytest.raises(ValueError):
        zero1_partition(-1, 2)
    with pytest.raises(ValueError):
        zero1_partition(10, 0)


def test_flatten_unflatten_roundtrip_bit_exact():
    template = param_template(CFG)
    flat = init_flat_params(CFG, params_seed=3)
    tree = unflatten_tree(flat, template)
    back = flatten_tree(tree)
    assert back.dtype == np.float32
    assert np.array_equal(back, flat)
    with pytest.raises(ValueError):
        unflatten_tree(flat[:-1], template)


def test_data_batch_is_a_pure_function_of_the_cursor():
    a_tok, a_tgt = data_batch(32, 8, 4, step=5, microbatch=2, seed=0)
    b_tok, b_tgt = data_batch(32, 8, 4, step=5, microbatch=2, seed=0)
    assert np.array_equal(a_tok, b_tok) and np.array_equal(a_tgt, b_tgt)
    assert a_tok.shape == (4, 8) and a_tgt.shape == (4, 8)
    assert a_tok.min() >= 0 and a_tok.max() < 32
    # targets are the next-token shift of the same underlying sequence
    c_tok, _ = data_batch(32, 8, 4, step=6, microbatch=2, seed=0)
    d_tok, _ = data_batch(32, 8, 4, step=5, microbatch=3, seed=0)
    assert not np.array_equal(a_tok, c_tok)
    assert not np.array_equal(a_tok, d_tok)


def test_elastic_off_training_is_unchanged(tmp_path):
    """Elastic-off guarantee: with the subsystem imported and its
    config built, a FaultTolerantTrainer run is bit-identical to one
    without any of that, and its scrape carries no training_elastic_*
    series (registration is lazy in the coordinator constructor)."""
    from deeplearning4j_tpu.datasets.iterators import BaseDatasetIterator
    from deeplearning4j_tpu.nn.conf.configuration import \
        NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers.feedforward import DenseLayer
    from deeplearning4j_tpu.nn.layers.output import OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.parallel.failure import FaultTolerantTrainer

    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 6)).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 64)]

    def _run(subdir, registry):
        conf = NeuralNetConfiguration(seed=7, updater="adam",
                                      learning_rate=0.01).list(
            DenseLayer(n_in=6, n_out=8, activation="tanh"),
            OutputLayer(n_out=2, activation="softmax",
                        loss_function="mcxent"))
        net = MultiLayerNetwork(conf).init()
        t = FaultTolerantTrainer(net, str(tmp_path / subdir),
                                 checkpoint_frequency=2,
                                 use_orbax=False, registry=registry)
        assert t.fit(BaseDatasetIterator(x, y, 16), epochs=1) is True
        return np.asarray(net.params_flat())

    a = _run("a", MetricsRegistry())
    # build the elastic config between the runs: merely touching the
    # subsystem must not perturb non-elastic training
    _ecfg(tmp_path)
    reg = MetricsRegistry()
    b = _run("b", reg)
    assert np.array_equal(a, b)
    assert "training_elastic" not in prometheus_text(reg)


# ---------------------------------------------------------------------------
# multiproc: real worker processes under membership change
# ---------------------------------------------------------------------------

def _coordinator(tmp_path, register, injector=None, registry=None,
                 recorder=None, **kw):
    ecfg = _ecfg(tmp_path, **kw)
    co = ElasticCoordinator(CFG, ecfg,
                            fault_injector=injector, registry=registry,
                            recorder=recorder)
    register(co)
    return co, ecfg


@pytest.mark.multiproc
def test_kill_and_rejoin_bit_reproducible(tmp_path):
    """SIGKILL one of three workers at step 3, admit a replacement at
    step 5: every loss and the final params bit-equal the
    uninterrupted oracle, and the measured per-worker updater bytes
    are the analytic 1/N contiguous shard."""
    rec = FlightRecorder(capacity=256)
    with child_killing_watchdog(HARD_TIMEOUT_S) as register:
        co, ecfg = _coordinator(
            tmp_path, register, recorder=rec, checkpoint_every=2,
            injector=ElasticFaultInjector(kill_at={3: 1}, join_at={5: 3}))
        out = co.run(8)
    ref = reference_run(CFG, ecfg, 8)
    assert out["losses"] == ref["losses"]
    assert np.array_equal(out["params"], ref["params"])
    assert out["workers"] == 3 and out["resizes"] == 2
    assert out["replayed_steps"] > 0
    acts = [e.data.get("action") for e in rec.recent(kind="elastic")]
    assert "kill_detected" in acts and "replay" in acts
    assert acts.count("resize") == 2
    # 1/N updater footprint: measured == analytic for every live worker
    n = out["n_params"]
    analytic = sorted(3 * 4 * (hi - lo)
                      for lo, hi in zero1_partition(n, 3))
    assert sorted(out["worker_state_bytes"].values()) == analytic
    assert sum(out["worker_state_bytes"].values()) == 3 * 4 * n


@pytest.mark.multiproc
def test_shrink_then_grow_bit_reproducible(tmp_path):
    """Shrink 3→2 (crash, no replacement) then grow 2→3: resharding
    is a pure function of membership size, so the whole trajectory
    stays bit-equal to the oracle."""
    with child_killing_watchdog(HARD_TIMEOUT_S) as register:
        co, ecfg = _coordinator(
            tmp_path, register,
            injector=ElasticFaultInjector(kill_at={2: 0}, join_at={5: 9}))
        out = co.run(8)
    ref = reference_run(CFG, ecfg, 8)
    assert out["losses"] == ref["losses"]
    assert np.array_equal(out["params"], ref["params"])
    assert out["workers"] == 3 and out["resizes"] == 2
    n = out["n_params"]
    assert sorted(out["worker_state_bytes"].values()) == sorted(
        3 * 4 * (hi - lo) for lo, hi in zero1_partition(n, 3))


@pytest.mark.multiproc
def test_loose_sync_engages_and_recovers(tmp_path):
    """A slowed worker misses `sync_every` barriers, drops to loose
    sync (typed events, stale counter), keeps training with zero lost
    steps, and resyncs to strict once un-slowed."""
    rec = FlightRecorder(capacity=256)
    reg = MetricsRegistry()
    with child_killing_watchdog(HARD_TIMEOUT_S) as register:
        co, _ = _coordinator(
            tmp_path, register, recorder=rec, registry=reg,
            injector=ElasticFaultInjector(
                slow_at={2: (1, 0.5), 6: (1, 0.0)}),
            step_timeout_s=0.15, sync_every=1, stale_bound=30)
        out = co.run(10)
    acts = [e.data.get("action") for e in rec.recent(kind="elastic")]
    assert "loose_enter" in acts and "resync" in acts
    assert "evict" not in acts
    assert len(out["losses"]) == 10          # zero lost steps
    assert np.isfinite(out["final_loss"])
    assert out["workers"] == 3
    assert reg.get("training_elastic_stale_steps_total").value > 0
    assert reg.get("training_elastic_workers").value == 3


@pytest.mark.multiproc
def test_hang_evicts_and_restores_bit_exactness(tmp_path):
    """A SIGSTOPped worker exhausts `stale_bound`, is evicted (ONE
    typed evict), and the lossy resize replays from the published
    checkpoint — discarding its loose steps restores bit-exactness
    against the 2-worker oracle tail."""
    rec = FlightRecorder(capacity=256)
    with child_killing_watchdog(HARD_TIMEOUT_S) as register:
        co, ecfg = _coordinator(
            tmp_path, register, recorder=rec,
            injector=ElasticFaultInjector(hang_at={3: 2}),
            step_timeout_s=0.15, sync_every=1, stale_bound=2)
        out = co.run(8)
    ref = reference_run(CFG, ecfg, 8)
    assert out["losses"] == ref["losses"]
    assert np.array_equal(out["params"], ref["params"])
    assert out["workers"] == 2 and out["replayed_steps"] > 0
    acts = [e.data.get("action") for e in rec.recent(kind="elastic")]
    assert acts.count("evict") == 1
    assert "replay" in acts
