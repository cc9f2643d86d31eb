"""The flash-attention kernels compiled for a described v5e, without the
chip (on-chip-measurement guide, section 2): what the chip's compiler
would refuse — a slice it cannot align, a panel stack past the scoped
VMEM — it refuses here. Nothing runs: this says nothing about results
or times. The topology is described inside a fixture, never at import,
and every such test lives in this one file (one process loads libtpu).
"""
import re
import sys

import jax
import jax.numpy as jnp
import pytest

import deeplearning4j_tpu.ops.flash_attention  # noqa: F401

# the ops package re-exports the FUNCTION under the module's name
fa = sys.modules["deeplearning4j_tpu.ops.flash_attention"]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no libtpu here, or another process has it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # and cannot be read back without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(autouse=True)
def _mosaic_kernels(monkeypatch):
    """The Mosaic calls themselves, whatever mode the run was given."""
    monkeypatch.setenv("DL4JTPU_FLASH", "auto")


def _compiled_grad(sharding, shape, **kw):
    """Compiled text of the gradient of all three inputs. Operands
    arrive as the projections leave them, [B, T, H*Dh]."""
    b, t, h, d = shape
    x = jax.ShapeDtypeStruct((b, t, h * d), jnp.bfloat16, sharding=sharding)

    def loss(q, k, v):
        q, k, v = (a.reshape(b, t, h, d) for a in (q, k, v))
        return jnp.sum(fa.flash_attention(q, k, v, **kw).astype(
            jnp.float32) ** 2)

    # the tests' x64 mode is not how the chip runs: Mosaic has no
    # 64-bit index arithmetic
    with jax.enable_x64(False):
        return jax.jit(jax.grad(loss, (0, 1, 2))).lower(
            x, x, x).compile().as_text()


def _kernel_operands(text):
    """Dimensions of every array a Mosaic call gives (its result types)
    or takes (its `operand_layout_constraints`)."""
    out = []
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            line = re.split(r"frontend_attributes|metadata=", line)[0]
            out += [tuple(int(n) for n in dims.split(","))
                    for dims in re.findall(r"(?:bf16|f32)\[([\d,]+)\]",
                                           line)]
    return out


@pytest.mark.parametrize("shape,kw", [
    # the training cell's (GPT-2 medium, 32 rows x 1024): a head pair a
    # program
    ((32, 1024, 16, 64), dict(causal=True)),
    # Cerebras-GPT-1.3B's and chip_smoke's: one head a program
    ((8, 2048, 16, 128), dict(causal=True)),
    # a ring-attention block, and rows that can be fully masked (m and
    # log l apart: four statistic rows a head pair)
    ((8, 1024, 16, 64), dict(causal=True, q_offset=1024)),
    ((8, 1024, 16, 64), dict(causal=True, kv_offset=512)),
    ((4, 1024, 8, 32), dict(causal=False)),
], ids=["gpt2m", "cgpt13", "ring_block", "masked_rows", "four_heads"])
def test_lane_dense_kernels_compile_for_v5e(one_chip, shape, kw):
    assert fa._lane_dense_width(shape[2], shape[3], shape[1], shape[1])
    text = _compiled_grad(one_chip, shape, **kw)
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    operands = _kernel_operands(text)
    # forward: q, k, v in, o and the statistics out; backward: q, k, v,
    # o, dO and the statistics in, dq, dk, dv out
    assert len(operands) == 14, operands
    # nothing the kernels move is padded: no minor dimension of 64 or
    # of 1 in a 128-lane tile
    for dims in operands:
        assert dims[-1] % 128 == 0, operands
    # and nothing around them is transposed or relaid to suit them
    relaid = [line.strip()[:160] for line in text.splitlines()
              if re.search(r"= \S+ (copy|transpose)\(", line)]
    assert not relaid, relaid


@pytest.mark.parametrize("shape", [
    (4, 1024, 8, 96),       # Dh neither divides 128 nor is divided by it
    (4, 64, 2, 16),         # a single short tile, H*Dh = 32
    (2, 4096, 16, 64),      # two k-superblocks in the backward
    (1, 8192, 8, 128),      # q-superblocks, the host's q x k tiling
], ids=["dh96", "t64", "t4096", "t8192"])
def test_per_head_kernels_compile_for_v5e(one_chip, shape):
    assert not fa._lane_dense_width(shape[2], shape[3], shape[1], shape[1])
    text = _compiled_grad(one_chip, shape, causal=True)
    assert text.count('custom_call_target="tpu_custom_call"') >= 2


def _kernel_names(text):
    """Which of the program's kernel names the compiled Mosaic calls
    carry (an instruction is named for its kernel, under whatever JAX
    transformation wrapped the call)."""
    calls = [line.split("=")[0] for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    return {name for name in ("flash_fwd", "flash_bwd", "gdn_fwd", "gdn_bwd",
                              "moe_gmm_fwd", "moe_gmm_dw", "moe_combine",
                              "ssd_fwd", "ssd_bwd")
            if any(name in c for c in calls)}


def test_grouped_query_kernels_compile_for_v5e(one_chip):
    """Qwen3-Next's full layer at the cell's size: 16 query heads on 2 KV
    heads of 256 at T 8192, a K/V head's block shared by its 8 query
    heads (one head an entry), resident K/V past the default of scoped
    VMEM."""
    b, t, h, hk, d = 3, 8192, 16, 2, 256
    q = jax.ShapeDtypeStruct((b, t, h, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, t, hk, d), jnp.bfloat16,
                              sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, causal=True).astype(
            jnp.float32) ** 2)

    with jax.enable_x64(False):
        text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
            q, kv, kv).compile().as_text()
    assert {"flash_fwd", "flash_bwd"} <= _kernel_names(text)
    # K and V reach the kernels with their 2 heads, never repeated to 16
    operands = _kernel_operands(text)
    # (the forward takes 24 query heads and their 3 KV heads a call,
    # twice: the grid cap, in whole groups)
    assert (24, t, d) in operands and (3, t, d) in operands
    assert (b * h, t, d) not in operands or (b * hk, t, d) in operands


def test_gated_delta_kernels_compile_for_v5e(one_chip, monkeypatch):
    """The chunked gated delta rule at the cell's size (16 key and 32 value
    heads of 128, 3 rows of 8192), forward and the written-out backward,
    on the bfloat16 operands the layer hands them, a key head's two value
    heads a program. The benchmark's `gdn_scan_*` metrics find the two
    kernels by their names."""
    import deeplearning4j_tpu.ops.gated_delta as gd
    from deeplearning4j_tpu.observability.tracing import default_spans
    from deeplearning4j_tpu.ops import pallas_util
    monkeypatch.setattr(pallas_util, "off_chip", lambda: False)
    b, t, hk, hv, d = 3, 8192, 16, 32, 128

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (sd((b, t, hk, d), jnp.bfloat16), sd((b, t, hk, d), jnp.bfloat16),
            sd((b, t, hv, d), jnp.bfloat16), sd((b, t, hv), jnp.float32),
            sd((b, t, hv), jnp.float32))

    def loss(*a):
        return jnp.sum(gd.gated_delta_rule(*a).astype(jnp.float32) ** 2)

    with jax.enable_x64(False):
        text = jax.jit(jax.grad(loss, (0, 1, 2, 3, 4))).lower(
            *args).compile().as_text()
    assert _kernel_names(text) == {"gdn_fwd", "gdn_bwd"}
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    last = [sp for sp in default_spans().snapshot().spans
            if sp.name == "gdn.layout"][-1]
    assert last.args == {"chunk": 64, "heads": hv, "block": 512,
                         "operands": "bfloat16", "heads_per_program": 2,
                         "inverse": "phased"}


def test_state_space_dual_kernels_compile_for_v5e(one_chip, monkeypatch):
    """Mamba-2's chunked scan at Granite's size (64 heads of 64, one group
    of B and C, state 128, 1 row of 8192), forward and the written-out
    backward, on the bfloat16 operands the layer hands them: `[B, T, 64 x
    64]` as the block leaves it, two heads a 128-lane group, nothing the
    kernels move with a minor dimension of 64 or of 1. The benchmark's
    `ssd_scan_*` metrics find the two kernels by their names."""
    import deeplearning4j_tpu.ops.mamba2_ssd as ssd
    from deeplearning4j_tpu.observability.tracing import default_spans
    from deeplearning4j_tpu.ops import pallas_util
    monkeypatch.setattr(pallas_util, "off_chip", lambda: False)
    b, t, h, p, n = 1, 8192, 64, 64, 128

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (sd((b, t, h, p), jnp.bfloat16), sd((b, t, 1, n), jnp.bfloat16),
            sd((b, t, 1, n), jnp.bfloat16), sd((b, t, h), jnp.float32),
            sd((b, t, h), jnp.float32))

    def loss(*a):
        return jnp.sum(ssd.ssd_scan(*a).astype(jnp.float32) ** 2)

    with jax.enable_x64(False):
        text = jax.jit(jax.grad(loss, (0, 1, 2, 3, 4))).lower(
            *args).compile().as_text()
    assert _kernel_names(text) == {"ssd_fwd", "ssd_bwd"}
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    operands = _kernel_operands(text)
    assert (b, t, h * p) in operands and (b, h, t // 128, 128) in operands
    for dims in operands:
        assert dims[-1] % 128 == 0, operands
    last = [sp for sp in default_spans().snapshot().spans
            if sp.name == "ssd.layout"][-1]
    assert last.args == {"chunk": 128, "heads": h, "head_dim": p, "state": n,
                         "groups": 1, "block": 1024, "operands": "bfloat16"}


def test_granites_attention_compiles_per_head_for_v5e(one_chip):
    """Granite's attention layer at the cell's size: 32 query heads on 8
    KV heads of 64 at T 8192 with the scale 1/64. A row of 8192 is past
    the lane-dense form's one superblock whatever the group, so it runs one
    head an entry of `[B*H, T, 64]` (PERF.md says what that costs), a KV
    head's block shared by its 4 query heads and never repeated."""
    b, t, h, hk, d = 1, 8192, 32, 8, 64
    assert not fa._lane_dense_width(h, d, t, t)
    q = jax.ShapeDtypeStruct((b, t, h, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, t, hk, d), jnp.bfloat16,
                              sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(
            q, k, v, causal=True, scale=1.0 / 64).astype(jnp.float32) ** 2)

    with jax.enable_x64(False):
        text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
            q, kv, kv).compile().as_text()
    assert {"flash_fwd", "flash_bwd"} <= _kernel_names(text)
    operands = _kernel_operands(text)
    assert (b * h, t, d) not in operands or (b * hk, t, d) in operands
    assert not [dims for dims in operands if dims == (b * h, t, d)
                and (b * hk, t, d) not in operands]


def test_grouped_matmul_kernels_compile_for_v5e(one_chip, monkeypatch):
    """The dropless experts at the cell's size, forward and backward: 32
    of 512 experts of 2048 x (2 x 512) held, 24,576 tokens x 10. Both
    branches are in the program: the first chunk of the capacity's
    39,168 rows (153 row tiles, 249 row blocks by token tile, bfloat16),
    and one chunk of the overflow path (the same rows, a float32 sum
    across chunks, an expert with no tile zeroed). The benchmark's
    `moe_experts_share.train` and `moe_gmm_roofline.train` find the
    grouped matmuls by `moe_gmm_`; the combine is named apart."""
    import deeplearning4j_tpu.ops.grouped_matmul as gm
    from deeplearning4j_tpu.ops import pallas_util
    monkeypatch.setattr(pallas_util, "off_chip", lambda: False)
    held, of, d, f, n, k = 32, 512, 2048, 512, 24576, 10
    rows = gm.capacity_rows(n * k, held, of)
    chunks = -(-gm.buffer_rows(n * k, held) // rows)
    assert (rows, chunks) == (39168, 7)

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    plan = gm.GroupPlan(sd((chunks, rows // gm.TILE_M), jnp.int32),
                        sd((1,), jnp.int32), sd((chunks, rows), jnp.int32),
                        sd((chunks, rows), jnp.int32),
                        sd((chunks, rows), jnp.bool_))

    def loss(x, w_gu, w_down, weight, plan):
        return jnp.sum(gm.dropless_experts(
            x, weight, w_gu, w_down, plan).astype(jnp.float32) ** 2)

    with jax.enable_x64(False):
        text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
            sd((n, d), jnp.bfloat16), sd((held, d, 2 * f), jnp.float32),
            sd((held, f, d), jnp.float32), sd((n, k), jnp.float32),
            plan).compile().as_text()
    assert {"moe_gmm_fwd", "moe_gmm_dw", "moe_combine"} <= _kernel_names(text)
    assert " conditional(" in text
    operands = _kernel_operands(text)
    # a chunk's rows, its rows by token tile, and nothing of the worst
    # case's 254,208 rows or of the 245,760 pairs
    assert (rows, d) in operands and (rows + n, d) in operands
    assert not [dims for dims in operands
                if dims[0] in (gm.buffer_rows(n * k, held), n * k)]


def test_rematerialised_step_holds_two_kernels_a_layer_pair_for_v5e(
        one_chip, monkeypatch):
    """A small GPT-2 step through `make_parallel_train_step` with
    `remat`, compiled whole: the scanned block's forward loop holds
    `flash_fwd`, its backward loop `flash_bwd` alone, because the
    checkpoint keeps the forward kernel's results (models/remat.py) and
    XLA drops the call from the recomputation. With the input kept alone
    the backward loop runs the forward kernel again: three."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from deeplearning4j_tpu.models import remat
    from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                       init_params)
    from deeplearning4j_tpu.parallel.megatron import (
        make_parallel_train_step, param_specs)
    from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
    from deeplearning4j_tpu.parallel.optim import AdamState
    # the program's kernel gate asks for the backend; this process is
    # held to the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = TransformerConfig(vocab_size=512, d_model=256, n_heads=4,
                            n_layers=4, max_len=256, dtype="bfloat16",
                            remat=True)
    mesh = make_mesh(MeshSpec(), devices=list(one_chip.device_set))

    def sd(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    params = jax.tree_util.tree_map(
        lambda leaf, spec: sd(leaf.shape, np.float32, spec),
        jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))),
        param_specs(cfg))
    opt = AdamState(m=params, v=params, count=sd((), np.int32, P()))
    toks = sd((2, 256), np.int32, P(("data",), ("seq",)))

    def kernels():
        step = make_parallel_train_step(cfg, mesh, learning_rate=1e-3)
        with jax.enable_x64(False):
            text = step.lower(params, opt, toks, toks).compile().as_text()
        assert _kernel_names(text) == {"flash_fwd", "flash_bwd"}
        return text.count('custom_call_target="tpu_custom_call"')

    assert kernels() == 2
    monkeypatch.setattr(remat, "RESIDUAL_NAMES", ())
    assert kernels() == 3
