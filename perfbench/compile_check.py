#!/usr/bin/env python3
"""Compiles a cell's programs at full size for a described v5e:2x2,
without the chip (on-chip-measurement guide, section 2), and prints the
compiler's memory analysis. What the chip's compiler would refuse (a kernel
it cannot tile, a program that does not fit) it refuses here, at no chip
time. Nothing runs: this is never a measurement.

    JAX_PLATFORMS=cpu python3 perfbench/compile_check.py --workload <name> \
        [--rows N] [--remat 0|1] [--remat-policy full|dots|mlp]
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def report(name: str, compiled, t: float) -> None:
    ma = compiled.memory_analysis()
    text = compiled.as_text()
    gib = 2.0 ** 30
    print(f"{name}: compiled for v5e:2x2 in {time.perf_counter() - t:.0f} s "
          f"(not a chip run): args {ma.argument_size_in_bytes / gib:.2f} "
          f"GiB, temp {ma.temp_size_in_bytes / gib:.2f} GiB, output "
          f"{ma.output_size_in_bytes / gib:.2f} GiB, alias "
          f"{ma.alias_size_in_bytes / gib:.2f} GiB a chip; kernels "
          f"{text.count('tpu_custom_call')}, collectives "
          f"{sum(text.count(c) for c in ('all-gather', 'reduce-scatter', 'all-reduce'))}",
          flush=True)


def described_devices(chips: int) -> list:
    """The first `chips` devices of a described v5e:2x2. The program's
    kernel gates ask jax.default_backend(); this process is held to the
    CPU, so the answer is steered here, in the script."""
    import jax
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    jax.default_backend = lambda: "tpu"
    return list(topo.devices)[:chips]


def serving(cell, tr: dict, args) -> int:
    """The two programs a paged, chunked-prefill engine runs, with the
    shapes `InferenceEngine.warmup` gives them."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    s = cell.sizes()
    ref = cell.reference()
    devices = described_devices(cell.chips)
    from deeplearning4j_tpu.models.transformer import page_pool_shape
    from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
    from deeplearning4j_tpu.parallel.serving import (
        make_paged_chunked_prefill, make_paged_decode, serving_param_specs)
    cfg = cell.program_config(s)
    mesh = make_mesh(MeshSpec(), devices=devices)
    e = dict(tr["engine"])
    if args.kv_pages:
        e["kv_pages"] = args.kv_pages
    ns, ps, npages = e["num_slots"], e["page_size"], e["kv_pages"]
    mp = -(-cfg.max_len // ps)
    c = e["prefill_chunk"]
    rep = NamedSharding(mesh, P())

    def sds(shape, dt, sh=rep):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)
    params = jax.tree_util.tree_map(
        lambda sh, sp: sds(sh, np.float32, NamedSharding(mesh, sp)),
        ref.leaf_shapes(s), serving_param_specs(cfg),
        is_leaf=lambda x: isinstance(x, tuple))
    pool = sds(page_pool_shape(cfg, npages, ps), jnp.bfloat16)
    vec = sds((ns,), np.int32)
    state = (pool, pool, vec, vec)
    bt = sds((ns, mp), np.int32)
    key = sds((2,), np.uint32)
    geo = (ns, ps, mp, npages)
    t = time.perf_counter()
    dec = make_paged_decode(cfg, mesh, 8, *geo)
    report(f"{cell.name} paged_decode kv_pages={npages}",
           dec.lower(params, *state, bt, sds((ns,), bool), vec,
                     key).compile(), t)
    t = time.perf_counter()
    pre = make_paged_chunked_prefill(cfg, mesh, c, *geo)
    report(f"{cell.name} paged_chunked_prefill kv_pages={npages}",
           pre.lower(params, *state, bt, sds((ns, c), np.int32), vec, vec,
                     sds((ns,), bool), key).compile(), t)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--benchmark", type=Path,
                    help="a file in BENCHMARK.json's shape, such as "
                         "perfbench/waiting.json, to find the cell in")
    ap.add_argument("--rows", type=int)
    ap.add_argument("--remat", type=int, choices=(0, 1))
    ap.add_argument("--remat-policy")
    ap.add_argument("--kv-pages", type=int)
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from perfbench.harness import cells

    cell = cells.Cell(args.workload, benchmark=args.benchmark)
    tr = dict(cell.traffic)
    if tr["kind"] != "train":
        return serving(cell, tr, args)
    for k, v in (("rows", args.rows), ("remat", args.remat),
                 ("remat_policy", args.remat_policy)):
        if v is not None:
            tr[k] = v
    s = cell.sizes()
    ref = cell.reference()
    devices = described_devices(cell.chips)

    from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
    from deeplearning4j_tpu.parallel.optim import AdamState
    from perfbench.harness import train
    cfg = train.train_config(cell, tr, s)
    shapes = ref.leaf_shapes(s)
    if tr["entry"] == "megatron":
        from deeplearning4j_tpu.parallel.megatron import (
            make_parallel_train_step, param_specs)
        mesh = make_mesh(MeshSpec(), devices=devices)
        shard = jax.tree_util.tree_map(
            lambda sp: NamedSharding(mesh, sp), param_specs(cfg),
            is_leaf=lambda x: not isinstance(x, dict))
        step = make_parallel_train_step(
            cfg, mesh, learning_rate=float(tr["learning_rate"]))
        batch_sh = NamedSharding(mesh, P(("data",), ("seq",)))
    else:
        from deeplearning4j_tpu.parallel.fsdp import (fsdp_shardings,
                                                      make_fsdp_train_step)
        mesh = make_mesh(MeshSpec(data=len(devices)), devices=devices)
        structs = jax.tree_util.tree_map(
            lambda sh: jax.ShapeDtypeStruct(sh, np.float32), shapes,
            is_leaf=lambda x: isinstance(x, tuple))
        shard = fsdp_shardings(structs, mesh)
        step = make_fsdp_train_step(
            cfg, mesh, learning_rate=float(tr["learning_rate"]))
        batch_sh = NamedSharding(mesh, P("data"))
    params = jax.tree_util.tree_map(
        lambda sh, sd: jax.ShapeDtypeStruct(sh, np.float32, sharding=sd),
        shapes, shard, is_leaf=lambda x: isinstance(x, tuple))
    opt = AdamState(m=params, v=params, count=jax.ShapeDtypeStruct(
        (), np.int32, sharding=NamedSharding(mesh, P())))
    batch = jax.ShapeDtypeStruct((int(tr["rows"]), int(tr["seq"])), np.int32,
                                 sharding=batch_sh)
    t = time.perf_counter()
    report(f"{cell.name} rows={tr['rows']} remat={tr['remat']}/"
           f"{tr.get('remat_policy')}",
           step.lower(params, opt, batch, batch).compile(), t)
    return 0


if __name__ == "__main__":
    sys.exit(main())
