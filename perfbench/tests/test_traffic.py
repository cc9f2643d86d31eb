"""Every traffic file offers the same work whatever the seed."""
import collections
import json
from pathlib import Path

import numpy as np
import pytest

from perfbench.harness import traffic as T

ROOT = Path(__file__).resolve().parents[1]
FILES = sorted((ROOT / "traffic").glob("*.json")) + \
    sorted((ROOT / "tests" / "data").glob("sessions-*.json"))
VOCAB = 50257


def load(path):
    return json.loads(path.read_text())


def first_blocks(tr, seed, n_blocks=2):
    """[(prompt_len, max_new, gap)] per block and the prompts, as offered."""
    if tr["kind"] == "sessions":
        gen = T.Sessions(tr, VOCAB, seed)
        reqs = gen.initial(1e9 if False else n_blocks * tr["block"]
                           / tr["rate_per_s"])
        by_block = collections.defaultdict(list)
        for r in reqs:
            plan = gen.live[r.conv]["plan"]
            by_block[r.block].append((plan[0], tuple(plan[1]),
                                      round(plan[2], 9)))
        return by_block, [r.prompt for r in reqs]
    stream = iter(T.RequestStream(tr, VOCAB, seed))
    by_block = collections.defaultdict(list)
    prompts, last = [], 0.0
    for _ in range(n_blocks * tr["block"]):
        r = next(stream)
        by_block[r.block].append((len(r.prompt), r.max_new,
                                  round(r.due_s - last, 9)))
        last = r.due_s
        prompts.append(r.prompt)
    return by_block, prompts


@pytest.mark.parametrize("path", FILES, ids=lambda p: p.stem)
def test_two_seeds_offer_the_same_multiset_in_another_order(path):
    tr = load(path)
    if tr["kind"] == "train":
        a = T.train_batches(dict(tr, rows=4, seq=128, block=2), VOCAB, 1)
        b = T.train_batches(dict(tr, rows=4, seq=128, block=2), VOCAB, 2)
        assert a[0].shape == b[0].shape == (2, 4, 128)
        assert not np.array_equal(a[0], b[0])
        # targets are the next token, across rows too
        assert np.array_equal(a[0].reshape(-1)[1:], a[1].reshape(-1)[:-1])
        rows = a[0].reshape(-1, 128)
        assert len({r.tobytes() for r in rows}) == len(rows)
        return
    blocks_a, prompts_a = first_blocks(tr, 11)
    blocks_b, prompts_b = first_blocks(tr, 2 ** 31 + 12)
    assert blocks_a.keys() == blocks_b.keys()
    complete = [b for b in blocks_a if len(blocks_a[b]) == tr["block"]]
    assert complete
    for b in complete:
        assert collections.Counter(blocks_a[b]) == \
            collections.Counter(blocks_b[b])
        # and every repetition of the block is the same multiset
        assert collections.Counter(blocks_a[b]) == \
            collections.Counter(blocks_a[complete[0]])
    if tr.get("seed_permutes", True):
        assert blocks_a[complete[0]] != blocks_b[complete[0]]   # the order
    else:
        assert blocks_a[complete[0]] == blocks_b[complete[0]]
    assert any(not np.array_equal(x, y)
               for x, y in zip(prompts_a, prompts_b))         # the ids


@pytest.mark.parametrize("path", [p for p in FILES
                                  if load(p)["kind"] == "open_loop"],
                         ids=lambda p: p.stem)
def test_a_block_spans_block_over_rate_seconds(path):
    tr = load(path)
    rows = T.block_rows(tr)
    assert sum(g for _, _, g in rows) == pytest.approx(
        tr["block"] / tr["rate_per_s"], rel=1e-9)


def test_no_request_outgrows_the_context():
    for path in FILES:
        tr = load(path)
        if tr["kind"] in ("open_loop", "closed_loop"):
            assert max(p + o for p, o, _ in T.block_rows(tr)) <= 2048


def test_sessions_turns_carry_the_conversation():
    tr = load(ROOT / "tests" / "data" / "sessions-example.json")
    gen = T.Sessions(tr, VOCAB, 3)
    first = gen.initial(30.0)
    assert first and all(r.turn == 0 for r in first)
    r = first[0]
    assert len(r.prompt) > tr["system_len"]
    assert any(np.array_equal(r.prompt[:tr["system_len"]], s)
               for s in gen.systems)
    answer = np.arange(r.max_new, dtype=np.int32)
    nxt = gen.on_finish(r, 10.0, answer)
    assert len(nxt) == 1 and nxt[0].turn == 1 and nxt[0].conv == r.conv
    think = gen.live[r.conv]["plan"][1][0][2]
    assert nxt[0].due_s == pytest.approx(10.0 + think)
    n = len(r.prompt) + len(answer)
    assert np.array_equal(nxt[0].prompt[:len(r.prompt)], r.prompt)
    assert np.array_equal(nxt[0].prompt[len(r.prompt):n], answer)
    # a conversation ends after its turns
    cur, turns = nxt[0], len(gen.live[r.conv]["plan"][1])
    for _ in range(turns - 2):
        cur = gen.on_finish(cur, 20.0, np.zeros(cur.max_new, np.int32))[0]
    assert gen.on_finish(cur, 30.0, np.zeros(cur.max_new, np.int32)) == []
