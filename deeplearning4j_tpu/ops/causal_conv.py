"""Pallas kernels for the depthwise causal convolution of the hybrid mixers,
with the elementwise work on either side of it, forward and backward.

Over a channel ``c`` of ``W`` taps a position ``t`` does

    p = pre * x                                  (pre-gate; p = x without)
    z = sum_i w[i] p[t - (W - 1) + i] + bias     (tap 0: the oldest position)
    a = silu(z)                                  (or a = z)
    y = post * a                                 (post-gate; y = a without)

Mamba-2 takes bias and SiLU, Gated DeltaNet SiLU, LFM2's gated short
convolution the two gates: which parts run is what the caller passes. The
taps, the gates and SiLU are float32, rounded once to the output's dtype;
the taps' and the bias's gradients are float32 sums over every position of
the batch.

Operands are read where they lie: an operand is an array ``[B, T, C]`` or a
`Lanes` view, channel ranges of a wider ``[B, T, L]`` (Mamba's ``x B C``
inside ``z x B C``, LFM2's three thirds of ``B C x~``, DeltaNet's q, k and v
heads inside ``q k v z``). A program is one (lane block of 128 k channels,
batch row, block of positions) and reaches a view's lanes through a table of
lane blocks in its index maps, so no operand is copied out in front of the
kernels. Positions run in order inside a program, a few rows at a time
(`SUB`). The forward carries the last 8 rows of ``p`` in VMEM from one block
of positions to the next, nought at a row's start; the backward walks the
blocks and their rows in reverse and carries the first 8 rows of ``dz`` the
same way, and reads the 16 rows before a block (its own `BlockSpec`) to
recompute ``p``'s taps there. A shift by ``s`` rows is a sublane roll with
its first or last tile taken from the carried rows, so no read from HBM is
unaligned. The backward keeps nothing of the forward's: it recomputes ``z``
from the operands, which are the residuals.

Off the chip, and for shapes the kernels do not take (channels that are not
whole 128-lane blocks, lanes read twice, rows not a multiple of 16, more
than 9 taps), the
same function is plain jnp (`_jnp_form`), as the mixers computed it before
the kernels: a padded float32 copy and a sum of shifted slices, the gates
and SiLU in the operands' dtype. That form is the tests' oracle.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array
F32 = jnp.float32
LANES = 128
TILE = 8                # rows of a float32 tile: the carried rows
HALO = 16               # rows before a block the backward reads (a bf16 tile)
MAX_LANE_BLOCK = 512    # lanes a program at most
BLOCK = 512 * 1024      # elements of an operand a program (rows x lanes)
SUB = 8192              # elements worked at once inside a program


class Lanes(NamedTuple):
    """Channels of `src` [B, T, L] read in place: its last axis's
    ``[start, stop)`` ranges, in this order, side by side."""
    src: Array
    ranges: Tuple[Tuple[int, int], ...]


def lanes(src: Array, *ranges: Tuple[int, int]) -> Lanes:
    return Lanes(src, tuple((int(a), int(b)) for a, b in ranges))


def _view(op) -> Optional[Lanes]:
    if op is None or isinstance(op, Lanes):
        return op
    return Lanes(op, ((0, op.shape[-1]),))


def _gather(op: Lanes) -> Array:
    parts = [op.src[..., a:b] for a, b in op.ranges]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, -1)


def _jnp_form(x: Array, w: Array, bias, act: bool, pre, post) -> Array:
    u = x if pre is None else pre * x
    width, t = w.shape[0], u.shape[1]
    up = jnp.pad(u, ((0, 0), (width - 1, 0), (0, 0))).astype(F32)
    y = sum(up[:, i:i + t] * w[i] for i in range(width))
    if bias is not None:
        y = y + bias
    y = y.astype(u.dtype)
    if act:
        y = jax.nn.silu(y)
    return y if post is None else post * y


# ---------------------------------------------------------------------------
# inside a program
# ---------------------------------------------------------------------------

def _rows(a: Array) -> Array:
    return jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)


def _down(p: Array, tail: Array, s: int) -> Array:
    """p s rows later: row r holds p[r - s], the first s rows from `tail`,
    the 8 rows before p."""
    from jax.experimental.pallas import tpu as pltpu
    if s == 0:
        return p
    rolled = pltpu.roll(p, s, 0)
    first = jnp.where(_rows(tail) < s, pltpu.roll(tail, s, 0),
                      rolled[:TILE])
    return jnp.concatenate([first, rolled[TILE:]], 0)


def _up(d: Array, head: Array, s: int) -> Array:
    """d s rows earlier: row r holds d[r + s], the last s rows from `head`,
    the 8 rows after d."""
    from jax.experimental.pallas import tpu as pltpu
    if s == 0:
        return d
    n = d.shape[0]
    rolled = pltpu.roll(d, n - s, 0)
    last = jnp.where(_rows(head) >= TILE - s, pltpu.roll(head, TILE - s, 0),
                     rolled[n - TILE:])
    return jnp.concatenate([rolled[:n - TILE], last], 0)


def _taps(p, tail, w, bias):
    width = w.shape[0]
    z = sum(w[i:i + 1] * _down(p, tail, width - 1 - i) for i in range(width))
    return z if bias is None else z + bias


def _tiles(a: Array) -> Array:
    """[n, lanes] summed to one float32 tile [8, lanes]: vector adds only."""
    return jnp.sum(a.reshape(a.shape[0] // TILE, TILE, a.shape[1]), axis=0)


class _Spec(NamedTuple):
    roles: Tuple          # x, pre, post: (source, offset in the table) | None
    ranges: Tuple         # x, pre, post: the view's channel ranges | None
    nc: int               # lane blocks of channels
    taps: int
    act: bool
    bias: bool
    lb: int               # lanes a program
    tb: int               # rows a program
    sub: int              # rows worked at once
    interpret: bool


def _take(it, present: bool):
    return next(it) if present else None


def _fwd_kernel(tbl_ref, *refs, spec: _Spec):
    import jax.experimental.pallas as pl
    it = iter(refs)
    x_ref = next(it)
    pre_ref = _take(it, spec.roles[1] is not None)
    post_ref = _take(it, spec.roles[2] is not None)
    w_ref = next(it)
    b_ref = _take(it, spec.bias)
    y_ref, tail_scr = next(it), next(it)

    @pl.when(pl.program_id(2) == 0)
    def _():
        tail_scr[...] = jnp.zeros_like(tail_scr)

    w = w_ref[...].astype(F32)
    bias = None if b_ref is None else b_ref[...].astype(F32)

    def body(i, tail):
        rows = pl.ds(pl.multiple_of(i * spec.sub, spec.sub), spec.sub)
        p = x_ref[0, rows, :].astype(F32)
        if pre_ref is not None:
            p = pre_ref[0, rows, :].astype(F32) * p
        a = _taps(p, tail, w, bias)
        if spec.act:
            a = a * jax.nn.sigmoid(a)
        if post_ref is not None:
            a = post_ref[0, rows, :].astype(F32) * a
        y_ref[0, rows, :] = a.astype(y_ref.dtype)
        return p[spec.sub - TILE:]

    tail_scr[...] = jax.lax.fori_loop(0, spec.tb // spec.sub, body,
                                      tail_scr[...])


def _bwd_kernel(tbl_ref, *refs, spec: _Spec):
    import jax.experimental.pallas as pl
    gated, posted = spec.roles[1] is not None, spec.roles[2] is not None
    it = iter(refs)
    x_ref, xh_ref = next(it), next(it)
    pre_ref, preh_ref = _take(it, gated), _take(it, gated)
    post_ref = _take(it, posted)
    dy_ref, w_ref = next(it), next(it)
    b_ref = _take(it, spec.bias)
    dx_ref = next(it)
    dpre_ref, dpost_ref = _take(it, gated), _take(it, posted)
    dw_ref = next(it)
    db_ref = _take(it, spec.bias)
    head_scr, accw_scr, accb_scr = next(it), next(it), next(it)

    row, blk = pl.program_id(1), pl.program_id(2)       # blk counts back
    last = (row == pl.num_programs(1) - 1) & (blk == pl.num_programs(2) - 1)

    @pl.when((row == 0) & (blk == 0))
    def _():
        accw_scr[...] = jnp.zeros_like(accw_scr)
        accb_scr[...] = jnp.zeros_like(accb_scr)

    @pl.when(blk == 0)
    def _():
        head_scr[...] = jnp.zeros_like(head_scr)

    w = w_ref[...].astype(F32)
    bias = None if b_ref is None else b_ref[...].astype(F32)
    width, sub = spec.taps, spec.sub

    def p_of(xv, prev):
        p = xv.astype(F32)
        return p if prev is None else prev.astype(F32) * p

    # p of the 8 rows before the block: nought before a row's first block
    halo = p_of(xh_ref[0], None if preh_ref is None else preh_ref[0])
    halo = jnp.where(blk == pl.num_programs(2) - 1, 0.0, halo[HALO - TILE:])

    def body(j, carry):
        head, accw, accb = carry
        i = spec.tb // sub - 1 - j
        rows = pl.ds(pl.multiple_of(i * sub, sub), sub)
        before = pl.ds(pl.multiple_of(jnp.maximum(i * sub - HALO, 0), HALO),
                       HALO)
        xv = x_ref[0, rows, :]
        bv = None if pre_ref is None else pre_ref[0, rows, :]
        p = p_of(xv, bv)
        tail = p_of(x_ref[0, before, :],
                    None if pre_ref is None else pre_ref[0, before, :])
        tail = jnp.where(i == 0, halo, tail[HALO - TILE:])
        z = _taps(p, tail, w, bias)
        dz = dy_ref[0, rows, :].astype(F32)
        if spec.act:
            sg = jax.nn.sigmoid(z)
            a = z * sg
        else:
            a = z
        if post_ref is not None:
            dpost_ref[0, rows, :] = (dz * a).astype(dpost_ref.dtype)
            dz = dz * post_ref[0, rows, :].astype(F32)
        if spec.act:
            dz = dz * (sg * (1.0 + z * (1.0 - sg)))
        accb = accb + _tiles(dz)
        accw = tuple(accw[k] + _tiles(dz * _down(p, tail, width - 1 - k))
                     for k in range(width))
        dp = sum(w[k:k + 1] * _up(dz, head, width - 1 - k)
                 for k in range(width))
        if pre_ref is None:
            dx_ref[0, rows, :] = dp.astype(dx_ref.dtype)
        else:
            dx_ref[0, rows, :] = (dp * bv.astype(F32)).astype(dx_ref.dtype)
            dpre_ref[0, rows, :] = (dp * xv.astype(F32)).astype(
                dpre_ref.dtype)
        return dz[:TILE], accw, accb

    head, accw, accb = jax.lax.fori_loop(
        0, spec.tb // sub, body,
        (head_scr[...], tuple(accw_scr[k] for k in range(width)),
         accb_scr[...]))
    head_scr[...] = head
    for k in range(width):
        accw_scr[k] = accw[k]
    accb_scr[...] = accb

    @pl.when(last)
    def _():
        for k in range(width):
            dw_ref[k:k + 1, :] = jnp.sum(accw[k], 0, keepdims=True).astype(
                dw_ref.dtype)
        if db_ref is not None:
            db_ref[...] = jnp.sum(accb, 0, keepdims=True).astype(db_ref.dtype)


# ---------------------------------------------------------------------------
# the calls
# ---------------------------------------------------------------------------

def _pallas(kernel, name, spec: _Spec, grid, tbl, ops, in_specs, out_shape,
            out_specs, scratch, semantics):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from deeplearning4j_tpu.ops.pallas_util import interpret_arg
    return pl.pallas_call(
        functools.partial(kernel, spec=spec),
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics, vmem_limit_bytes=32 * 2 ** 20),
        interpret=interpret_arg(spec.interpret, *ops),
        name=name,
    )(tbl, *ops)


def _fwd_call(spec: _Spec, tbl, srcs, w, bias):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from deeplearning4j_tpu.ops.pallas_util import out_struct
    b, t, _ = srcs[spec.roles[0][0]].shape
    lb, tb, nc = spec.lb, spec.tb, spec.nc

    def operand(role):
        src, off = role
        return pl.BlockSpec((1, tb, lb),
                            lambda c, r, i, tbl: (r, i, tbl[off + c]))

    ops, in_specs = [], []
    for role in spec.roles:
        if role is not None:
            ops.append(srcs[role[0]])
            in_specs.append(operand(role))
    ops.append(w)
    in_specs.append(pl.BlockSpec((spec.taps, lb), lambda c, r, i, tbl: (0, c)))
    if spec.bias:
        ops.append(bias.reshape(1, -1))
        in_specs.append(pl.BlockSpec((1, lb), lambda c, r, i, tbl: (0, c)))
    x = srcs[spec.roles[0][0]]
    return _pallas(
        _fwd_kernel, "causal_conv_fwd", spec, (nc, b, t // tb), tbl, ops,
        in_specs, out_struct((b, t, nc * lb), x.dtype, *ops),
        pl.BlockSpec((1, tb, lb), lambda c, r, i, tbl: (r, i, c)),
        [pltpu.VMEM((TILE, lb), F32)],
        ("parallel", "parallel", "arbitrary"))


def _bwd_call(spec: _Spec, tbl, srcs, w, bias, dy):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from deeplearning4j_tpu.ops.pallas_util import out_struct
    b, t, _ = dy.shape
    lb, tb, nc = spec.lb, spec.tb, spec.nc
    nt = t // tb

    def back(i):
        return nt - 1 - i

    def operand(role):
        src, off = role
        return pl.BlockSpec((1, tb, lb),
                            lambda c, r, i, tbl: (r, back(i), tbl[off + c]))

    def before(role):           # the 16 rows in front of the block
        src, off = role
        return pl.BlockSpec(
            (1, HALO, lb), lambda c, r, i, tbl: (
                r, jnp.maximum(back(i) * (tb // HALO) - 1, 0),
                tbl[off + c]))

    x_role, pre_role, post_role = spec.roles
    ops = [srcs[x_role[0]], srcs[x_role[0]]]
    in_specs = [operand(x_role), before(x_role)]
    if pre_role is not None:
        ops += [srcs[pre_role[0]], srcs[pre_role[0]]]
        in_specs += [operand(pre_role), before(pre_role)]
    if post_role is not None:
        ops.append(srcs[post_role[0]])
        in_specs.append(operand(post_role))
    own = pl.BlockSpec((1, tb, lb), lambda c, r, i, tbl: (r, back(i), c))
    ops += [dy, w]
    in_specs += [own,
                 pl.BlockSpec((spec.taps, lb), lambda c, r, i, tbl: (0, c))]
    lane_row = pl.BlockSpec((1, lb), lambda c, r, i, tbl: (0, c))
    if spec.bias:
        ops.append(bias.reshape(1, -1))
        in_specs.append(lane_row)
    out_shape, out_specs = [], []
    for role in spec.roles:
        if role is not None:
            out_shape.append(out_struct((b, t, nc * lb),
                                        srcs[role[0]].dtype, *ops))
            out_specs.append(own)
    out_shape.append(out_struct(w.shape, w.dtype, *ops))
    out_specs.append(
        pl.BlockSpec((spec.taps, lb), lambda c, r, i, tbl: (0, c)))
    if spec.bias:
        out_shape.append(out_struct((1, nc * lb), bias.dtype, *ops))
        out_specs.append(lane_row)
    return _pallas(
        _bwd_kernel, "causal_conv_bwd", spec, (nc, b, nt), tbl, ops,
        in_specs, out_shape, out_specs,
        [pltpu.VMEM((TILE, lb), F32),
         pltpu.VMEM((spec.taps, TILE, lb), F32),
         pltpu.VMEM((TILE, lb), F32)],
        ("parallel", "arbitrary", "arbitrary"))


# ---------------------------------------------------------------------------
# the differentiable call
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _core(spec: _Spec, table: Tuple[int, ...], srcs, w, bias):
    return _fwd_call(spec, jnp.asarray(table, jnp.int32), srcs, w, bias)


def _core_fwd(spec, table, srcs, w, bias):
    return _core(spec, table, srcs, w, bias), (srcs, w, bias)


def _core_bwd(spec, table, res, dy):
    srcs, w, bias = res
    grads = list(_bwd_call(spec, jnp.asarray(table, jnp.int32), srcs, w,
                           bias, dy))
    by_role = [grads.pop(0) if role is not None else None
               for role in spec.roles]
    dw = grads.pop(0)
    db = grads.pop(0).reshape(bias.shape) if spec.bias else None
    return _scatter(spec, srcs, by_role), dw, db


_core.defvjp(_core_fwd, _core_bwd)


def _scatter(spec: _Spec, srcs, by_role):
    """Each source's cotangent: the roles' channel-major gradients put back
    on the lanes they were read from, nought on the others."""
    out = []
    for j, src in enumerate(srcs):
        pieces = []
        for role, ranges, g in zip(spec.roles, spec.ranges, by_role):
            if role is None or role[0] != j:
                continue
            at = 0
            for a, b in ranges:
                pieces.append((a, b, g[..., at:at + b - a]))
                at += b - a
        pieces.sort(key=lambda piece: piece[0])
        parts, at = [], 0
        for a, b, g in pieces:
            if a > at:
                parts.append(jnp.zeros(src.shape[:-1] + (a - at,), src.dtype))
            parts.append(g)
            at = b
        if at < src.shape[-1]:
            parts.append(jnp.zeros(src.shape[:-1] + (src.shape[-1] - at,),
                                   src.dtype))
        out.append(parts[0] if len(parts) == 1
                   else jnp.concatenate(parts, -1))
    return tuple(out)


def _tiling(views, w: Array):
    """(lanes, rows) a program, or None where the kernels cannot take the
    shapes: every range whole lane blocks and no lane read twice (a
    source's cotangent is its ranges' gradients side by side), rows a
    multiple of 16, the carried 8 rows enough for the taps."""
    b, t = views[0].src.shape[:2]
    edges = {e for v in views for rng in v.ranges for e in rng}
    read = [(id(v.src), lane) for v in views for a, c in v.ranges
            for lane in range(a, c, LANES)]
    if (t % HALO or w.shape[0] - 1 > TILE or len(set(read)) < len(read)
            or any(e % LANES for e in edges)
            or any(v.src.ndim != 3 or v.src.shape[:2] != (b, t)
                   for v in views)):
        return None
    lb = MAX_LANE_BLOCK
    while any(e % lb for e in edges):
        lb //= 2
    tb = max(HALO, min(t, BLOCK // lb) // HALO * HALO)
    while t % tb:
        tb -= HALO
    return lb, tb


def _count(path: str, bias, act: bool, pre, post) -> None:
    from deeplearning4j_tpu.observability.metrics import default_registry
    epilogue = "+".join(part for part, on in (
        ("bias", bias is not None), ("silu", act), ("gate", post is not None))
        if on) or "none"
    default_registry().counter(
        "causal_conv_calls", "causal convolutions traced, by the path taken "
        "and the elementwise work before and after the taps",
        labelnames=("path", "prologue", "epilogue")).labels(
            path, "gate" if pre is not None else "none", epilogue).inc()


def causal_conv(x, w: Array, bias: Array = None, *, act: str = None,
                pre=None, post=None, kernel=None) -> Array:
    """Depthwise causal convolution of x [B, T, C] by w [W, C] (tap 0
    multiplies the oldest position), bias [C] or none, then `act` (None or
    "silu"), with an optional gate on either side: ``post * act(conv(pre *
    x) + bias)``. x, `pre` and `post` are arrays [B, T, C] or `Lanes` views
    of C channels. The result is in x's dtype.

    `kernel`: None takes the Pallas kernels on a TPU wherever the shapes
    allow (`_tiling`) and the jnp form elsewhere; True forces the kernels
    (through the interpreter off the chip), False the jnp form."""
    from deeplearning4j_tpu.observability.tracing import mark
    from deeplearning4j_tpu.ops.pallas_util import off_chip
    if act not in (None, "silu"):
        raise ValueError(f"causal_conv: act {act!r}: expected None or 'silu'")
    views = [_view(op) for op in (x, pre, post)]
    live = [v for v in views if v is not None]
    widths = {sum(b - a for a, b in v.ranges) for v in live}
    if len(widths) != 1 or widths != {w.shape[1]}:
        raise ValueError(f"causal_conv: operands of {sorted(widths)} "
                         f"channels for taps of {w.shape[1]}")
    tiling = _tiling(live, w)
    if kernel and tiling is None:
        raise ValueError("causal_conv: the kernels take whole 128-lane "
                         "blocks of channels, each read once, and rows in "
                         "multiples of 16")
    use = kernel if kernel is not None else (
        tiling is not None and not off_chip())
    _count("kernel" if use else "jnp", bias, act is not None, pre, post)
    if not use:
        x, pre, post = (None if v is None else _gather(v) for v in views)
        return _jnp_form(x, w, bias, act is not None, pre, post)

    lb, tb = tiling
    srcs, roles, table = [], [], []
    for v in views:
        if v is None:
            roles.append(None)
            continue
        j = next((i for i, s in enumerate(srcs) if s is v.src), None)
        if j is None:
            j = len(srcs)
            srcs.append(v.src)
        roles.append((j, len(table)))
        table += [blk for a, b in v.ranges for blk in range(a // lb, b // lb)]
    sub = max(HALO, min(tb, SUB // lb))
    while tb % sub:
        sub -= HALO
    mark("causal_conv.layout", channels=w.shape[1], taps=w.shape[0],
         lane_block=lb, t_block=tb)
    spec = _Spec(tuple(roles), tuple(None if v is None else v.ranges
                                     for v in views),
                 w.shape[1] // lb, w.shape[0], act is not None,
                 bias is not None, lb, tb, sub, off_chip())
    return _core(spec, tuple(table), tuple(srcs), w, bias)
