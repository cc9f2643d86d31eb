"""A configuration, a traffic mix, a per-layer metric and a cell are each
added as new files plus one new entry, with no edit to a file that is
there."""
import hashlib
import json
import shutil
from pathlib import Path

import pytest

from perfbench.harness import cells

ROOT = Path(__file__).resolve().parents[1]


def digest(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts and ".cache" not in p.parts
            and ".out" not in p.parts}


def copied_tree(tmp_path):
    """A copy of perfbench/ to add files to, the digest of what is there,
    and BENCHMARK.json's entries to append to."""
    copy = tmp_path / "repo"
    shutil.copytree(ROOT, copy / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", ".cache",
                                                  ".out"))
    bench = json.loads((ROOT.parent / "BENCHMARK.json").read_text())
    return copy, digest(copy / "perfbench"), bench


def test_a_dummy_of_each_is_found_without_editing_a_file(tmp_path):
    copy, before, bench = copied_tree(tmp_path)
    old_cell = bench["workloads"][0]["name"]

    pb = copy / "perfbench"
    cfg = json.loads((pb / "configs" / "cerebras-gpt-1.3b.json").read_text())
    cfg.update(name="dummy-model", n_layer=3)
    (pb / "configs" / "dummy-model.json").write_text(json.dumps(cfg))
    tr = json.loads((pb / "traffic" / "chat-short.json").read_text())
    tr.update(rate_per_s=9.0)
    (pb / "traffic" / "dummy-mix.json").write_text(json.dumps(tr))
    (pb / "metrics" / "dummy_metric.json").write_text(json.dumps(
        {"reader": "dummy_reader", "args": {"times": 3}}))
    (pb / "readers" / "dummy_reader.py").write_text(
        "def read(run, args):\n    return run['x'] * args['times']\n")
    (pb / "metrics" / "dummy_silent.json").write_text(json.dumps(
        {"reader": "device_idle_share", "args": {}}))

    bench["configs"].append({"name": "dummy-model", "source": "none",
                             "file": "perfbench/configs/dummy-model.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "dummy-cell", "config": "dummy-model",
                               "traffic": "dummy-mix", "chips": 1,
                               "why": "a test"})
    bench["end_to_end"].append(
        {"name": "dummy_rate", "unit": "tokens/s", "better": "higher",
         "bound": 0.01, "source": "host_clock", "workloads": ["dummy-cell"]})
    for name in ("dummy_metric", "dummy_silent"):
        bench["per_layer"].append(
            {"name": name, "unit": "count", "better": "higher",
             "source": "program_counter", "layer": "load generator",
             "moves": "dummy_rate", "workloads": ["dummy-cell"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = cells.Cell("dummy-cell", benchmark=copy / "BENCHMARK.json",
                      root=pb)
    assert cell.config["n_layer"] == 3
    assert cell.traffic["rate_per_s"] == 9.0
    names = [m["name"] for m in cell.per_layer()]
    assert "dummy_metric" in names and "compile_in_window" not in names
    assert [m["name"] for m in cell.end_to_end()] == ["setup_s",
                                                      "dummy_rate"]
    got = cell.read_per_layer({"x": 7.0})
    # a reader that finds nothing to read leaves its metric out of the line
    assert got == {"dummy_metric": {"value": 21.0, "unit": "count"}}
    # and the old cell is still found, with its own metrics and no new one
    old = cells.Cell(old_cell, benchmark=copy / "BENCHMARK.json", root=pb)
    assert old.traffic["kind"] == "train"
    assert "dummy_metric" not in [m["name"] for m in old.per_layer()]

    after = digest(pb)
    assert {k: v for k, v in after.items() if k in before} == before


DUMMY_ARCH = '''"""A family the harness has never heard of: the GPT-2 block
with a Switch MLP, `n_experts` of them, one a token."""
import dataclasses

from perfbench.harness.arith import causal_pairs


@dataclasses.dataclass(frozen=True)
class Sizes:
    n_layer: int
    n_embd: int
    n_head: int
    n_inner: int
    n_experts: int
    vocab_size: int
    n_positions: int
    eps: float


def sizes(cfg):
    return Sizes(eps=float(cfg["layer_norm_epsilon"]), **{
        k: int(cfg[k]) for k in ("n_layer", "n_embd", "n_head", "n_inner",
                                 "n_experts", "vocab_size", "n_positions")})


def rehearsal(cfg):
    return cfg["rehearsal"]             # a block of the file, this family


def program_config(cfg, s, **training):
    from deeplearning4j_tpu.models.transformer import TransformerConfig
    return TransformerConfig(
        vocab_size=s.vocab_size, d_model=s.n_embd, n_heads=s.n_head,
        n_layers=s.n_layer, max_len=s.n_positions,
        mlp_ratio=s.n_inner // s.n_embd, eps=s.eps, n_experts=s.n_experts,
        dtype=cfg["activation_dtype"], **training)


def train_flops_per_step(s, rows, t):
    """A token meets the router's n_experts columns and one expert."""
    per_layer = (4 * s.n_embd * s.n_embd + s.n_embd * s.n_experts
                 + 2 * s.n_embd * s.n_inner)
    weights = s.n_layer * per_layer + s.n_embd * s.vocab_size
    return 3.0 * (2.0 * weights * rows * t
                  + 4.0 * s.n_embd * s.n_layer * rows * causal_pairs(t))
'''

DUMMY_REFERENCE = '''"""The family's leaves, for the weights: the GPT-2
block's with a router and stacked experts where its MLP was."""
from pathlib import Path

from perfbench.harness.cells import load_module

_base = load_module(Path(__file__).with_name("gpt2_block.py"),
                    "perfbench_reference_dummy_base")
_dense_leaf_shapes = _base.leaf_shapes


def leaf_shapes(s):
    d, f, L, e = s.n_embd, s.n_inner, s.n_layer, s.n_experts
    base = _dense_leaf_shapes(s)
    blocks = {k: v for k, v in base["blocks"].items()
              if k not in ("W1", "b1", "W2", "b2")}
    blocks.update(router=(L, d, e), We1=(L, e, d, f), We2=(L, e, f, d))
    return dict(base, blocks=blocks)


_base.leaf_shapes = leaf_shapes         # the initialiser looks it up there
make_init, seed_key = _base.make_init, _base.seed_key
'''


def test_a_dummy_family_reaches_the_program_and_the_arithmetic(tmp_path):
    """A block other than the one that is there: an arch module, a
    reference, a configuration and a traffic file, all new, and keys that
    no file that is there has heard of reach the program's config, its
    built step and the reader's arithmetic."""
    import numpy as np

    from perfbench.harness import arith, traffic, train, xplane
    copy, before, bench = copied_tree(tmp_path)
    pb = copy / "perfbench"
    (pb / "archs" / "dummy_block.py").write_text(DUMMY_ARCH)
    (pb / "references" / "dummy_block.py").write_text(DUMMY_REFERENCE)
    cfg = json.loads((pb / "configs" / "gpt2-medium.json").read_text())
    cfg.update(name="dummy-moe", arch="dummy_block", reference="dummy_block",
               n_experts=8,
               rehearsal=dict(n_layer=2, n_embd=64, n_head=2, n_inner=128,
                              n_experts=2, vocab_size=256, n_positions=64))
    (pb / "configs" / "dummy-moe.json").write_text(json.dumps(cfg))
    tr = json.loads((pb / "traffic" / "train-t1024-dense.json").read_text())
    tr.update(rows=16)
    (pb / "traffic" / "dummy-train.json").write_text(json.dumps(tr))
    bench["configs"].append({"name": "dummy-moe", "source": "none",
                             "file": "perfbench/configs/dummy-moe.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "dummy-moe-train",
                               "config": "dummy-moe",
                               "traffic": "dummy-train", "chips": 1,
                               "why": "a test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("train_tokens_per_s", "train_step_mfu.train",
                         "flash_attn_roofline.train"):
            m["workloads"].append("dummy-moe-train")
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = cells.Cell("dummy-moe-train", benchmark=copy / "BENCHMARK.json",
                      root=pb)
    full, s = cell.sizes(), cell.sizes(rehearse=True)
    assert (full.n_experts, full.n_embd) == (8, 1024)
    assert (s.n_experts, s.n_embd) == (2, 64)
    # a static argument of jitted steps: equal sizes are one cache key
    assert hash(s) == hash(cell.sizes(rehearse=True)) and s != full
    tr = train.shrink_traffic(cell.traffic)
    assert train.train_config(cell, cell.traffic, full).n_experts == 8
    prog = train.train_config(cell, tr, s)
    assert (prog.n_experts, prog.d_model, prog.remat) == (2, 64, True)

    # the program's step, built by the harness for this family, runs
    step, params, opt, _, _ = train._build(cell, tr, s, 7, cell.reference())
    assert params["blocks"]["We1"].shape == (2, 2, 64, 128)
    tokens, targets = traffic.train_batches(tr, s.vocab_size, 7)
    _, _, loss = step(params, opt, tokens[0], targets[0])
    assert np.isfinite(float(loss)) and 4.0 < float(loss) < 7.0

    # the step's MFU is of this family's work: its own count, through the
    # reader that is there
    red = xplane.Reduced()
    red.window_s = 4.0
    red.devices.append({"name": "/device:TPU:0", "ops": {}, "op_events": [],
                        "busy_s": 3.0, "modules": [
                            ("jit_step(1)", float(i), 0.9) for i in range(3)]})
    kind = next(iter(arith.PEAKS))
    run = {"trace": red, "cell": cell, "sizes": full, "rows": 16,
           "seq": 1024, "chips": 1, "device_kind": kind}
    got = cell.read_per_layer(run)
    flops = cell.arch().train_flops_per_step(full, 16, 1024)
    assert got["train_step_mfu.train"]["value"] == pytest.approx(
        100 * flops / (1.0 * arith.PEAKS[kind]["flops_per_s"]))
    old = cells.Cell(bench["workloads"][0]["name"],
                     benchmark=copy / "BENCHMARK.json", root=pb)
    assert flops == old.count("train_flops_per_step")(old.sizes(), 16, 1024) \
        + 3.0 * 2.0 * 24 * 1024 * 8 * 16 * 1024
    # a count that the family has not written is an error that names the
    # module and the function, not a metric silently left out
    red.devices[0]["op_events"] = [
        ("tpu_custom_call:flash_fwd_", "%flash_fwd.1", 0.1, 0.2)]
    with pytest.raises(SystemExit, match=r"archs/dummy_block\.py has no "
                                         r"flash_train_roofline_s\(\)"):
        cell.read_per_layer(run)

    after = digest(pb)
    assert {k: v for k, v in after.items() if k in before} == before


@pytest.mark.parametrize("benchmark", ["BENCHMARK.json",
                                       "perfbench/waiting.json"])
def test_every_named_file_exists(benchmark):
    """In BENCHMARK.json, and in the file of the cells that wait."""
    path = ROOT.parent / benchmark
    bench = json.loads(path.read_text())
    reported = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        cell = cells.Cell(w["name"], benchmark=path)
        assert cell.per_layer() and len(cell.end_to_end()) >= 2
        for m in cell.per_layer():
            assert m["moves"] in reported
            spec = json.loads((ROOT / "metrics" / f"{m['name']}.json")
                              .read_text())
            assert (ROOT / "readers" / f"{spec['reader']}.py").is_file()
    for c in bench["configs"]:
        assert json.loads((ROOT.parent / c["file"]).read_text())["name"] == \
            c["name"]
        assert any(w["config"] == c["name"] for w in bench["workloads"])
