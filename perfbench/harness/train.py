"""Drives a training cell: the program's jitted train step on its mesh.

Set-up builds one object, the step with its state, drives it from the seed
through its first three steps (the ones the reference follows) through the
window's own call and feed, and hands that same object to the window. The
rate is the tokens of every step that ended inside the window over the
measured window, the last step closed by `block_until_ready`.
"""
from __future__ import annotations

import gc
import shutil
import time
from typing import List

import numpy as np

from perfbench.harness import traffic as traffic_mod
from perfbench.harness.serve import Tracer

now = time.perf_counter
ADAM_B1 = 0.9


def shrink_traffic(t: dict) -> dict:
    t = dict(t, rows=4, seq=64, block=4, xent_chunk=0)
    t.pop("eot_id", None)
    t["doc_len"] = dict(t["doc_len"], median=24, min=4, max=64)
    t["check"] = dict(t["check"], ref_rows_per_block=2,
                      limits=t["check"]["rehearsal_limits"])
    return t


def train_config(cell, tr: dict, s):
    return cell.program_config(s, remat=bool(tr["remat"]),
                               remat_policy=tr.get("remat_policy", "full"),
                               xent_chunk=int(tr["xent_chunk"]))


def _build(cell, tr: dict, s, seed: int, ref):
    """The program's step and its state, weights made on the device from
    the seed in the step's own shardings."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
    from deeplearning4j_tpu.parallel.optim import AdamState, init_adam_state

    cfg = train_config(cell, tr, s)
    lr = float(tr["learning_rate"])
    devices = jax.devices()[:cell.chips]
    if tr["entry"] == "megatron":
        from deeplearning4j_tpu.parallel.megatron import (
            make_parallel_train_step, param_specs)
        mesh = make_mesh(MeshSpec(), devices=devices)
        shardings = jax.tree_util.tree_map(
            lambda sp: NamedSharding(mesh, sp), param_specs(cfg),
            is_leaf=lambda x: not isinstance(x, dict))
        step = make_parallel_train_step(cfg, mesh, learning_rate=lr)
    elif tr["entry"] == "fsdp":
        from deeplearning4j_tpu.parallel.fsdp import (fsdp_shardings,
                                                      make_fsdp_train_step)
        mesh = make_mesh(MeshSpec(data=len(devices)), devices=devices)
        shapes = jax.tree_util.tree_map(
            lambda sh: jax.ShapeDtypeStruct(sh, np.float32),
            ref.leaf_shapes(s), is_leaf=lambda x: isinstance(x, tuple))
        shardings = fsdp_shardings(shapes, mesh)
        step = make_fsdp_train_step(cfg, mesh, learning_rate=lr)
    else:
        raise SystemExit(f"perfbench: unknown training entry "
                         f"{tr['entry']!r}")
    init = ref.make_init(s, shardings)
    params = jax.block_until_ready(init(ref.seed_key(seed)))
    # Adam's zeros in one jitted call, born in the parameters' shardings:
    # made leaf by leaf they were some forty small compilations, 35-40 s of
    # every set-up on the chip, none of them long enough to be cached
    rep = NamedSharding(mesh, PartitionSpec())
    opt = jax.jit(init_adam_state, out_shardings=AdamState(
        m=shardings, v=shardings, count=rep))(params)
    return step, params, opt, init, mesh


def run(cell, args, ctx) -> dict:
    import jax

    ref = cell.reference()
    s = ctx["sizes"]
    tr = shrink_traffic(cell.traffic) if args.rehearse else cell.traffic
    split = ctx["split"]
    rows, seq = int(tr["rows"]), int(tr["seq"])

    t = now()
    import deeplearning4j_tpu.parallel  # noqa: F401  (timed: PERF.md says why)
    split["import_program"] = now() - t
    t = now()
    step, params, opt, init, mesh = _build(cell, tr, s, args.seed, ref)
    split["weights"] = now() - t
    t = now()
    tokens, targets = traffic_mod.train_batches(tr, s.vocab_size, args.seed)
    nb = tokens.shape[0]
    split["data"] = now() - t

    def feed(i: int):
        return tokens[i % nb], targets[i % nb]

    # the first three steps, the ones the reference follows
    t = now()
    got = first_steps(ref, step, params, opt, [feed(i) for i in range(3)],
                      init, args.seed, split)
    params, opt = got.pop("state")
    n_steps = 3
    loss = None
    for _ in range(2):                        # warm the steady feed
        params, opt, loss = step(params, opt, *feed(n_steps))
        n_steps += 1
    jax.block_until_ready(loss)
    split["first_steps"] = now() - t - split["trace_lower_compile_or_load"]

    tracer = None
    t0 = now()
    if args.trace:
        trace_dir = ctx["out_dir"] / f"trace-{cell.name}"
        shutil.rmtree(trace_dir, ignore_errors=True)
        t0 = now() + 2.0
        tracer = Tracer(trace_dir, t0,
                        min(float(tr["trace_s"]), args.seconds))
        tracer.start()
        while now() < t0:                     # keep the device fed
            params, opt, loss = step(params, opt, *feed(n_steps))
            n_steps += 1
            jax.block_until_ready(loss)
        t0 = now()
    ctx["compiles"].mark("open")
    ctx["setup_s"] = t0 - ctx["t_start"]

    # the window: one step in flight behind the one being dispatched
    ends: List[float] = []
    window_losses = []
    prev = None
    while True:
        params, opt, loss = step(params, opt, *feed(n_steps))
        n_steps += 1
        if prev is not None:
            jax.block_until_ready(prev)
            ends.append(now())
            window_losses.append(prev)
            if ends[-1] - t0 >= args.seconds:
                break
        prev = loss
    jax.block_until_ready(loss)
    t1 = now()
    ends.append(t1)
    window_losses.append(loss)
    ctx["compiles"].mark("close")
    if tracer is not None:
        tracer.join(timeout=120)
        if tracer.error is not None or tracer.t_b is None:
            raise SystemExit(f"perfbench: tracing failed: {tracer.error!r}")
    last_losses = [float(x) for x in window_losses[-3:]]

    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in mesh.devices.flat)
    del params, opt, loss, prev, window_losses, step
    gc.collect()

    t = now()
    compared = check_trained(
        ref, s, [feed(i) for i in range(3)], tr, args.seed, got, mesh)
    ctx["reference_s"] = now() - t

    steps_in = len(ends)
    return {"kind": "training", "t0": t0, "t1": t1, "step_ends": ends,
            "summary": {
                "window_s": t1 - t0, "attempted": steps_in,
                "failed": int(sum(not np.isfinite(x) for x in last_losses)),
                "tokens": steps_in * rows * seq,
                "tokens_per_s": steps_in * rows * seq / (t1 - t0)},
            "tokens_per_step": rows * seq, "rows": rows, "seq": seq,
            "tracer": tracer, "memory_peak_bytes": peak,
            "compared": compared, "sizes": s, "traffic": tr,
            "chips": cell.chips}


def first_steps(ref, step, params, opt, batches, init, seed: int,
                split: dict = None) -> dict:
    """The program's first three steps through its own call: each loss, and
    from its state the first gradient as the optimizer got it (Adam's first
    moment after one step is (1 - b1) times it): leaf norms, and a sample
    of every leaf for its direction; then the leaf norms of the parameters'
    change after the three, against the seed's initial weights."""
    import jax
    t = now()
    leaf_norms = jax.jit(ref.leaf_norms)
    samples = jax.jit(ref.leaf_samples)
    losses = []
    grad1 = dirs = None
    for i, (tok, tgt) in enumerate(batches):
        params, opt, loss = step(params, opt, tok, tgt)
        losses.append(float(loss))
        if i == 0:
            if split is not None:
                split["trace_lower_compile_or_load"] = now() - t
            grad1 = np.asarray(leaf_norms(opt.m)) / (1.0 - ADAM_B1)
            dirs = [np.asarray(x) for x in samples(opt.m)]
    p0 = init(ref.seed_key(seed))
    change = np.asarray(jax.jit(ref.diff_norms)(params, p0))
    del p0
    return {"losses": losses, "grad1": grad1, "dirs": dirs,
            "change": change, "state": (params, opt)}


def _ref_shardings(ref, s, mesh):
    """Where the reference's state does not fit one chip it is spread over
    the cell's chips: each leaf split along its last axis that divides."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    n = mesh.devices.size
    if n == 1:
        return None, None

    def spec(shape):
        for ax in reversed(range(len(shape))):
            if shape[ax] % n == 0 and shape[ax] >= n:
                return NamedSharding(mesh, P(*([None] * ax + ["data"])))
        return NamedSharding(mesh, P())

    tree = jax.tree_util.tree_map(spec, ref.leaf_shapes(s),
                                  is_leaf=lambda x: isinstance(x, tuple))
    return tree, NamedSharding(mesh, P())


def reference_readings(ref, s, batches, tr: dict, seed: int, mesh,
                       precision: str = "f32", halve_batch: bool = False):
    """Losses of the first three steps, leaf norms of the first gradient and
    of the parameters' change after the three, by the plain reference (or,
    at a lower `precision` or with half the batch left out, by a control)."""
    import jax
    import jax.numpy as jnp

    shardings, batch_sh = _ref_shardings(ref, s, mesh)
    init = ref.make_init(s, shardings)
    rpb = int(tr["check"]["ref_rows_per_block"])
    step = ref.make_train_step(s, float(tr["learning_rate"]), rpb, precision,
                               shardings, batch_sh)
    with jax.default_matmul_precision("highest"):
        params = init(ref.seed_key(seed))
        m = jax.tree_util.tree_map(jnp.zeros_like, params)
        v = jax.tree_util.tree_map(jnp.zeros_like, params)
        losses, grad1, dirs = [], None, None
        for i, (tok, tgt) in enumerate(batches):
            if halve_batch:
                tok, tgt = tok[:len(tok) // 2], tgt[:len(tgt) // 2]
            params, m, v, loss, gn, gs = step(params, m, v, tok, tgt,
                                              jnp.float32(i + 1))
            losses.append(float(loss))
            if i == 0:
                grad1 = np.asarray(gn)
                dirs = [np.asarray(x) for x in gs]
        del m, v
        change = np.asarray(jax.jit(ref.diff_norms)(
            params, init(ref.seed_key(seed))))
    return {"losses": losses, "grad1": grad1, "dirs": dirs,
            "change": change}


def worst_leaf_gap(got: np.ndarray, want: np.ndarray,
                   keep: np.ndarray):
    """The gap between the two norms of a leaf, not the norm of their
    difference, against the reference's norm of that leaf or of the median
    leaf, whichever is larger; the worst leaf."""
    floor = float(np.median(want))
    gap = np.where(keep, np.abs(got - want) / np.maximum(want, floor), 0.0)
    return float(np.max(gap)), int(np.argmax(gap))


def compare_readings(got: dict, want: dict, limits: dict,
                     names=None) -> dict:
    """Each number compared, beside its limit. Leaves whose gradient is
    nought to rounding in the reference (under a thousandth of the median
    leaf's) move under Adam by round-off alone and are left out of the
    change."""
    out = {}
    for i in range(3):
        # a step's loss is compared where the readings gave it a limit
        # (PERF.md names the one they did not)
        if f"loss_step{i + 1}" not in limits:
            continue
        rel = abs(got["losses"][i] - want["losses"][i]) / abs(
            want["losses"][i])
        out[f"loss_step{i + 1}"] = {"value": float(rel),
                                    "limit": limits[f"loss_step{i + 1}"]}
    everything = np.ones_like(want["grad1"], bool)
    moved = want["grad1"] >= 1e-3 * np.median(want["grad1"])
    for key, series, keep in (("grad_norm_gap", "grad1", everything),
                              ("update_norm_gap", "change", moved)):
        value, leaf = worst_leaf_gap(got[series], want[series], keep)
        out[key] = {"value": value, "limit": limits[key]}
        if names is not None:
            out[key]["leaf"] = names[leaf]
    # the first gradient's direction, by the median leaf: 1 - cosine of the
    # two sides' samples. Rounding turns a gradient without changing its
    # length, which the gaps of norms above cannot see.
    import jax
    turn = np.asarray(jax.jit(direction_gaps)(got["dirs"], want["dirs"]))
    out["grad_direction_gap"] = {
        "value": float(np.median(turn[moved])),
        "limit": limits["grad_direction_gap"]}
    return out


def direction_gaps(a, b):
    """1 - cosine between two sets of leaf samples, a row each, as one flat
    vector in the order of the leaf norms."""
    import jax.numpy as jnp
    out = []
    for x, y in zip(a, b):
        dot = jnp.sum(x * y, axis=1)
        nn = jnp.sqrt(jnp.sum(x * x, axis=1) * jnp.sum(y * y, axis=1))
        out.append(1.0 - dot / jnp.maximum(nn, 1e-30))
    return jnp.concatenate(out)


def check_trained(ref, s, batches, tr, seed, got, mesh) -> dict:
    want = reference_readings(ref, s, batches, tr, seed, mesh)
    return compare_readings(got, want, tr["check"]["limits"],
                            ref.leaf_names(s))


def is_correct(compared: dict) -> bool:
    return all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in compared.values())
