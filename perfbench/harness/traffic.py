"""One general traffic generator, driven by a traffic file.

A traffic file does not give distributions to draw from. It gives
distributions and a block size, and the generator lays a fixed quantile grid
of each distribution over a block; the quantities are paired by fixed
permutations written in the file. The stream is that block repeated. The
seed permutes the order inside each repetition of the block and draws the
token ids, and nothing else: every seed offers the same multiset of (prompt
length, output length, gap) per block, so the same prompt tokens, output
tokens and span of arrivals. A file may also fix the order
(`"seed_permutes": false`, the order under `pairing.order`); the seed then
draws the token ids only.

Kinds (`"kind"` in the file):
  open_loop    requests arrive on a schedule, whether or not earlier ones
               have finished
  closed_loop  `clients` requests are outstanding at any time; the next of
               the stream is sent the moment one ends
  sessions     conversations arrive on a schedule; each turn's prompt is the
               conversation so far (shared system prompt, earlier turns and
               the served answers) plus new user tokens, sent a think time
               after the last answer ended
  train        batches of packed documents for a training step
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Iterator, List, Optional

import numpy as np


def quantile_grid(spec: dict, n: int) -> List[float]:
    """n values at the quantiles (i + 1/2) / n of the distribution `spec`,
    clipped to [min, max]."""
    qs = [(i + 0.5) / n for i in range(n)]
    dist = spec["dist"]
    if dist == "lognormal":
        mu, sigma = math.log(spec["median"]), spec["sigma"]
        inv = statistics.NormalDist().inv_cdf
        vals = [math.exp(mu + sigma * inv(q)) for q in qs]
    elif dist == "uniform":
        lo, hi = spec["min"], spec["max"]
        vals = [lo + (hi - lo) * q for q in qs]
    elif dist == "exponential":
        vals = [-math.log(1.0 - q) for q in qs]
        scale = spec.get("mean", 1.0) * n / sum(vals)   # exact mean
        vals = [v * scale for v in vals]
    elif dist == "fixed":
        vals = [spec["value"]] * n
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    lo, hi = spec.get("min", -math.inf), spec.get("max", math.inf)
    return [min(max(v, lo), hi) for v in vals]


def int_grid(spec: dict, n: int) -> List[int]:
    return [int(round(v)) for v in quantile_grid(spec, n)]


def pairing(traffic: dict, name: str, n: int) -> List[int]:
    perm = traffic.get("pairing", {}).get(name)
    if perm is None:
        return list(range(n))
    if sorted(perm) != list(range(n)):
        raise ValueError(f"pairing.{name} is not a permutation of 0..{n-1}")
    return list(perm)


@dataclasses.dataclass
class Request:
    """One request as offered. `due_s` is seconds after the stream's start
    (None: due the moment the generator says so)."""
    seq: int
    prompt: np.ndarray
    max_new: int
    due_s: Optional[float]
    block: int
    conv: Optional[int] = None
    turn: int = 0


def block_rows(traffic: dict) -> List[tuple]:
    """The block's fixed multiset: (prompt_len, output_len, gap_s) rows,
    in the file's order (before the seed permutes it)."""
    n = int(traffic["block"])
    prompts = int_grid(traffic["prompt_len"], n)
    outs = int_grid(traffic["output_len"], n)
    po = pairing(traffic, "output_len", n)
    if traffic["kind"] == "open_loop":
        mean_gap = 1.0 / float(traffic["rate_per_s"])
        gaps = quantile_grid(dict(traffic["gap"], mean=mean_gap), n)
        pg = pairing(traffic, "gap", n)
    else:
        gaps, pg = [0.0] * n, list(range(n))
    return [(prompts[i], outs[po[i]], gaps[pg[i]]) for i in range(n)]


class RequestStream:
    """The block repeated, each repetition in an order drawn from the seed,
    token ids drawn from the seed. Arrival times accumulate the gaps."""

    def __init__(self, traffic: dict, vocab: int, seed: int):
        self.rows = block_rows(traffic)
        self.vocab = vocab
        self.rng = np.random.default_rng([int(seed), 0x7EF1C])
        # `seed_permutes: false` keeps the file's own order (`pairing.order`)
        # for every seed: where a window holds about one block, the order
        # decides which requests share the pool, and with it the rate
        self.fixed = (None if traffic.get("seed_permutes", True)
                      else pairing(traffic, "order", len(self.rows)))
        self.seq = 0
        self.block = 0
        self.clock = 0.0

    def __iter__(self) -> Iterator[Request]:
        while True:
            order = (self.rng.permutation(len(self.rows))
                     if self.fixed is None else self.fixed)
            for i in order:
                plen, olen, gap = self.rows[i]
                self.clock += gap
                yield Request(
                    seq=self.seq,
                    prompt=self.rng.integers(0, self.vocab, plen,
                                             dtype=np.int32),
                    max_new=olen, due_s=self.clock, block=self.block)
                self.seq += 1
            self.block += 1


class OpenLoop:
    """Arrivals on a schedule, up to `horizon_s` after the stream's start."""

    def __init__(self, traffic: dict, vocab: int, seed: int):
        self.stream = iter(RequestStream(traffic, vocab, seed))

    def initial(self, horizon_s: float) -> List[Request]:
        out = []
        while True:
            r = next(self.stream)
            if r.due_s >= horizon_s:
                return out
            out.append(r)

    def on_finish(self, req: Request, now_s: float, answer) -> List[Request]:
        return []


class ClosedLoop:
    """`clients` requests outstanding; the stream's next is due the moment
    one ends. Which client sends it makes no difference to the work."""

    def __init__(self, traffic: dict, vocab: int, seed: int):
        self.stream = iter(RequestStream(traffic, vocab, seed))
        self.clients = int(traffic["clients"])

    def _next(self, due_s: float) -> Request:
        r = next(self.stream)
        r.due_s = due_s
        return r

    def initial(self, horizon_s: float) -> List[Request]:
        return [self._next(0.0) for _ in range(self.clients)]

    def on_finish(self, req: Request, now_s: float, answer) -> List[Request]:
        return [self._next(now_s)]


class Sessions:
    """Conversations on a schedule. A block is `block` conversations; the
    grids give each its system prompt (round robin over `system_prompts`),
    its number of turns, and per turn the user tokens, the answer length
    and the think time. The seed permutes the conversations of a block and
    draws token ids. A turn's prompt is everything so far, capped at
    `context_cap` less the answer (a capped conversation ends)."""

    def __init__(self, traffic: dict, vocab: int, seed: int):
        self.t = traffic
        self.vocab = vocab
        self.rng = np.random.default_rng([int(seed), 0x5E55])
        n = int(traffic["block"])
        self.n = n
        sys_rng = np.random.default_rng(int(traffic["system_seed"]))
        self.systems = [sys_rng.integers(0, vocab,
                                         int(traffic["system_len"]),
                                         dtype=np.int32)
                        for _ in range(int(traffic["system_prompts"]))]
        turns = int_grid(traffic["turns"], n)
        pt = pairing(traffic, "turns", n)
        max_turns = max(turns)
        m = n * max_turns
        user = int_grid(traffic["user_len"], m)
        ans = int_grid(traffic["answer_len"], m)
        think = quantile_grid(traffic["think_s"], m)
        pu, pa, pk = (pairing(traffic, k, m)
                      for k in ("user_len", "answer_len", "think_s"))
        mean_gap = 1.0 / float(traffic["rate_per_s"])
        gaps = quantile_grid(dict(traffic["gap"], mean=mean_gap), n)
        pg = pairing(traffic, "gap", n)
        self.convs = []
        for c in range(n):
            steps = [(user[pu[c * max_turns + j]], ans[pa[c * max_turns + j]],
                      think[pk[c * max_turns + j]])
                     for j in range(turns[pt[c]])]
            self.convs.append((c % len(self.systems), steps, gaps[pg[c]]))
        self.live = {}
        self.seq = 0
        self.block = 0
        self.clock = 0.0
        self.next_conv = 0

    def rows(self) -> List[tuple]:
        """The block's multiset, for the tests: one row a conversation."""
        return [(sysid, tuple(steps), gap) for sysid, steps, gap in self.convs]

    def _request(self, conv: int, turn: int, context: np.ndarray,
                 due_s: float) -> Optional[Request]:
        sysid, steps, _ = self.live[conv]["plan"]
        if turn >= len(steps):
            return None
        user, ans, _ = steps[turn]
        prompt = np.concatenate(
            [context, self.rng.integers(0, self.vocab, user,
                                        dtype=np.int32)])
        if len(prompt) + ans > int(self.t["context_cap"]):
            return None
        r = Request(seq=self.seq, prompt=prompt, max_new=ans, due_s=due_s,
                    block=self.live[conv]["block"], conv=conv, turn=turn)
        self.seq += 1
        return r

    def initial(self, horizon_s: float) -> List[Request]:
        out = []
        while True:
            order = self.rng.permutation(self.n)
            for i in order:
                plan = self.convs[i]
                self.clock += plan[2]
                if self.clock >= horizon_s:
                    return out
                conv = self.next_conv
                self.next_conv += 1
                self.live[conv] = {"plan": plan, "block": self.block}
                out.append(self._request(conv, 0, self.systems[plan[0]],
                                         self.clock))
            self.block += 1

    def on_finish(self, req: Request, now_s: float, answer) -> List[Request]:
        _, steps, _ = self.live[req.conv]["plan"]
        think = steps[req.turn][2]
        context = np.concatenate([req.prompt,
                                  np.asarray(answer, np.int32)])
        nxt = self._request(req.conv, req.turn + 1, context, now_s + think)
        if nxt is None:
            del self.live[req.conv]
            return []
        return [nxt]


SERVING_KINDS = {"open_loop": OpenLoop, "closed_loop": ClosedLoop,
                 "sessions": Sessions}


def train_batches(traffic: dict, vocab: int, seed: int):
    """`block` batches [rows, seq] of documents packed end to end, each
    closed by the end-of-text id; targets are the next token. Document
    lengths are a quantile grid; the seed permutes them and draws the ids.
    Rows all differ. The window cycles through the block."""
    rows, t = int(traffic["rows"]), int(traffic["seq"])
    nb = int(traffic["block"])
    rng = np.random.default_rng([int(seed), 0x7A1])
    need = nb * rows * t + 1
    mean = traffic["doc_len"]["median"]
    n_docs = int(need / mean * 2) + 8
    lens = int_grid(traffic["doc_len"], n_docs)
    lens = [lens[i] for i in rng.permutation(n_docs)]
    eot = int(traffic.get("eot_id", vocab - 1))
    flat = np.empty(need, np.int32)
    at = 0
    for n in lens:
        if at >= need:
            break
        n = min(n, need - at)
        flat[at:at + n] = rng.integers(0, eot, n, dtype=np.int32)
        flat[at + n - 1] = eot
        at += n
    if at < need:
        raise ValueError("document grid too short for the block")
    tokens = flat[:-1].reshape(nb, rows, t)
    targets = flat[1:].reshape(nb, rows, t)
    return tokens, targets
