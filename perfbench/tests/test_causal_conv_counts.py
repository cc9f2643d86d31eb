"""The causal convolution's count of required work in the three cells that
run it (`perfbench/counts/<arch>.py`), worked by hand, and
`causal_conv_roofline.train` read from made-up traces: what the kernels
would take at the byte peak, remat's second forward included, reads under
100%; a program without the kernels reads nothing."""
import pytest

from perfbench.harness import cells, xplane

KIND = "TPU v5 lite"
SEQ = 8192
BW, PEAK = 819e9, 197e12

# cell: (rows, layers, channels, taps, float32 numbers of the taps and bias
# a layer, passes of [rows x 8192, channels] in bfloat16 a layer)
HAND = {
    # x B C = 4096 + 2 x 128 channels: forward reads it and writes the
    # output, backward reads it and the output's gradient, writes x B C's
    "granite4h-train-t8192-1chip": (1, 9, 4352, 4, 5, 5),
    # B, C and x~ read and y written forward; B, C, x~ and dy read and
    # dB, dC and dx~ written backward
    "lfm2a2b-train-ep8share-1chip": (4, 4, 2048, 3, 3, 11),
    # q, k (16 heads of 128 each) and v (32 of 128) of every DeltaNet layer
    "qwen3next-train-ep16share-1chip": (3, 3, 8192, 4, 4, 5),
}


def count_of(cell):
    from perfbench.readers import kernel_roofline_counts as reader
    return reader._Counts(cell).count("causal_conv_train_roofline_s")


@pytest.mark.parametrize("name", sorted(HAND))
def test_the_count_by_hand(name):
    cell = cells.Cell(name)
    rows, layers, c, w, floats, passes = HAND[name]
    assert cell.traffic["rows"] == rows and cell.traffic["seq"] == SEQ
    nbytes = passes * rows * SEQ * c * 2 + 3 * 4 * floats * c
    flops = 6 * w * c * rows * SEQ
    assert nbytes / BW > flops / PEAK          # bound by its bytes
    got = count_of(cell)(cell.sizes(), rows, SEQ, KIND)
    assert got == pytest.approx(layers * nbytes / BW, rel=1e-12)


def test_granites_count_in_bytes():
    """9 layers x 5 passes of [8192, 4352] bfloat16: 3.21 GB, 3.92 ms (the
    taps and the bias add 0.07%)."""
    cell = cells.Cell("granite4h-train-t8192-1chip")
    got = count_of(cell)(cell.sizes(), 1, SEQ, KIND)
    assert got == pytest.approx(9 * 5 * 8192 * 4352 * 2 / BW, rel=1e-3)
    assert got * BW == pytest.approx(3.21e9, rel=1e-3)


FUSED = ("%causal_conv_bwd.14 = (f32[3,4,4352]{2,1,0}, bf16[1,8192,4352]"
         "{2,1,0}) fusion(%p.1, %p.2), kind=kCustom, calls=%fused.7")


def test_a_kernel_fused_into_the_loop_keeps_its_name():
    assert xplane.op_key(FUSED) == "causal_conv_bwd_f32_3_4_4352_"


def made_up_trace(fwd_s: float, bwd_s: float, period: float = 0.45,
                  window: float = 4.0, kernels: bool = True):
    red = xplane.Reduced()
    red.window_s = window
    steps = window / period
    events = [("fusion_bf16_1_8192_8448_", "%fusion.1", 0.0, 0.5)]
    if kernels:
        # the scanned layers' backward call as XLA leaves it in a loop: in
        # one fusion with the stacking of the taps' gradient, named for it
        # and with no `tpu_custom_call` in its key
        events += [("tpu_custom_call:causal_conv_fwd_bf16_1_8192_4352_",
                    "%causal_conv_fwd.1", 1.0, fwd_s * steps),
                   ("tpu_custom_call:causal_conv_bwd_bf16_1_8192_4352_",
                    "%causal_conv_bwd.1", 2.0, bwd_s * steps / 3),
                   (xplane.op_key(FUSED), FUSED, 3.0, 2 * bwd_s * steps / 3)]
    red.devices.append({
        "name": "/device:TPU:0", "ops": {}, "busy_s": window,
        "op_events": events,
        "modules": [("jit_step(1)", 0.05 + period * i, period * 0.99)
                    for i in range(int(steps))]})
    return red


def a_run(cell, red, rows):
    return {"trace": red, "cell": cell, "sizes": cell.sizes(), "rows": rows,
            "seq": SEQ, "chips": 1, "device_kind": KIND,
            "compile_in_window": 0, "memory_peak_bytes": 1,
            "span_snapshot": type("S", (), {"spans": []})()}


def roofline(cell, red, rows):
    got = cell.read_per_layer(a_run(cell, red, rows))
    return got.get("causal_conv_roofline.train", {}).get("value")


@pytest.mark.parametrize("name", sorted(HAND))
def test_a_kernel_at_the_byte_peak_reads_under_a_hundred(name):
    """Kernels that move each byte at 819 GB/s, the forward twice a step
    (remat's second forward): the recomputed forward adds seconds and no
    work, so the reading is the required bytes over the moved ones."""
    cell = cells.Cell(name)
    rows, layers, c, _, _, passes = HAND[name]
    fwd_passes = {5: 2, 11: 4}[passes]
    pass_s = layers * rows * SEQ * c * 2 / BW
    got = roofline(cell, made_up_trace(2 * fwd_passes * pass_s,
                                       (passes - fwd_passes) * pass_s), rows)
    assert got == pytest.approx(
        100.0 * count_of(cell)(cell.sizes(), rows, SEQ, KIND)
        / ((passes + fwd_passes) * pass_s), rel=1e-3)
    assert 60 < got < 100


def test_a_program_without_the_kernels_reads_nothing():
    """The parent's program: the metric is left out of the line."""
    cell = cells.Cell("granite4h-train-t8192-1chip")
    assert roofline(cell, made_up_trace(0, 0, kernels=False), 1) is None


@pytest.mark.parametrize("name", ["gpt2m-train-1chip",
                                  "glm47flash-train-ep8share-1chip"])
def test_the_cells_without_the_convolution_do_not_list_it(name):
    listed = {m["name"] for m in cells.Cell(name).per_layer()}
    assert not {"causal_conv_roofline.train",
                "mamba_conv_share.train"} & listed
