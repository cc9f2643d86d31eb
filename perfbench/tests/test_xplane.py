"""The `.xplane.pb` reduction on a small trace recorded on a v5e chip (a
two-layer model's training step through the program's Megatron entry, 8
steps inside a 51 ms `perfbench_window`; kept gzipped beside this file)."""
import gzip
import shutil
from pathlib import Path

import pytest

from perfbench.harness import xplane

DATA = Path(__file__).resolve().parent / "data" / \
    "train-tiny-v5e.xplane.pb.gz"


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(DATA, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return xplane.reduce(path)


def test_interval_arithmetic():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.2, 3.4)]
    assert xplane.union_seconds(iv) == pytest.approx(3.0)
    assert xplane.gaps(iv, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0),
                                          (4.0, 5.0)]


def test_operation_keys_survive_renumbering():
    k = xplane.op_key
    assert k("%fusion.12 = bf16[32,16,128]{2,1,0:T(8,128)(2,1)} fusion(x)") \
        == k("%fusion.977 = bf16[32,16,128]{2,1,0} fusion(y)") \
        == "fusion_bf16_32_16_128_"
    assert k('%closed_call.3 = (bf16[4,256,128]{2,1,0}, f32[4,256,1]{2,1,0})'
             ' custom-call(a), custom_call_target="tpu_custom_call"') == \
        "tpu_custom_call:closed_call_bf16_4_256_128_"
    assert k("jit_step(436928131399911333)") == "jit_step(436928131399911333)"


def test_window_busy_and_idle(reduced):
    assert len(reduced.devices) == 1
    assert reduced.window_s == pytest.approx(0.0512, abs=0.0005)
    busy = reduced.busy_s
    assert 0.001 < busy < 0.002                 # 8 steps of ~0.17 ms
    assert 100 * (1 - busy / reduced.window_s) == pytest.approx(97.1,
                                                                abs=0.3)
    # the parts add up to the whole: nested operations are not counted twice
    assert sum(reduced.ops_total().values()) == pytest.approx(busy, rel=0.01)


def test_programs_and_kernels(reduced):
    runs = reduced.module_runs("jit_step")
    assert len(runs) == 8
    for start, dur in runs:
        assert 0.00015 < dur < 0.0002
    assert reduced.module_runs("jit_nothing") == []
    # the three Pallas kernels of the flash attention: forward, its
    # rematerialised rerun, and the fused backward
    kernels = {k: v for k, v in reduced.ops_total().items()
               if k.startswith("tpu_custom_call:")}
    assert set(kernels) == {
        "tpu_custom_call:closed_call_bf16_4_256_128_",
        "tpu_custom_call:rematted_computation_bf16_4_256_128_",
        "tpu_custom_call:checkpoint_bf16_4_256_128_"}
    assert reduced.op_seconds("^tpu_custom_call:") == pytest.approx(
        sum(kernels.values()), rel=0.01)
    assert reduced.op_seconds("no_such_operation") is None


def test_idle_gaps_are_named_by_what_the_host_did(reduced):
    b = reduced.breakdown()
    assert len(b["device_ops"]) == 10 and b["idle_gaps"]
    assert sum(v for _, v in b["idle_gaps"]) <= reduced.window_s
    assert b["idle_gaps"][0][1] > 0.04          # the sleeping feed loop
