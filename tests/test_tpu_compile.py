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
