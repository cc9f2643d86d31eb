"""What every `pallas_call` site in ops/ needs from the installed JAX
(0.9.0) and would otherwise spell three times."""
from __future__ import annotations

import jax


def out_struct(shape, dtype, *operands) -> jax.ShapeDtypeStruct:
    """`out_shape` entry whose varying-manual-axes set is the union of
    the operands': inside `jax.shard_map(check_vma=True)` (every
    serving program, parallel/serving.py) a pallas_call output must say
    over which mesh axes it varies, and a kernel's result varies
    wherever any of its inputs does. Outside shard_map every operand's
    set is empty and the field is ignored."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def off_chip() -> bool:
    """True where the backend is not a TPU: kernels that have no jnp twin
    (ops/gated_delta.py, ops/grouped_matmul.py) then run through the
    Pallas interpreter, which is how the CPU tests and rehearsals reach
    them."""
    return jax.default_backend() != "tpu"


def interpret_arg(interpret: bool, *operands):
    """`pallas_call(interpret=...)` value for the CPU test mode
    (DL4JTPU_FLASH / DL4JTPU_FUSED_LSTM = interpret).

    Plain `True` — the HLO interpreter — wherever it works: it is fast
    and has no side effects, so it composes with `jax.checkpoint` (the
    remat'd training step). It does not work inside
    `shard_map(check_vma=True)`: it re-evaluates the kernel jaxpr on the
    caller's vma-typed blocks and fails on the first varying-times-
    literal product. Operands that carry a vma therefore get the TPU
    interpreter, which keeps kernel values out of the caller's trace
    (through io_callbacks — which is why it cannot sit under remat)."""
    if not interpret:
        return False
    if any(jax.typeof(x).vma for x in operands):
        from jax.experimental.pallas import tpu as pltpu
        return pltpu.InterpretParams()
    return True
