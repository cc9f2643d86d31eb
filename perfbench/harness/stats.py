"""Rates and percentiles from a client-side event log.

A rate is all the work over all the time: every output token produced
inside the window as the client saw it committed, from requests finished or
not, over the measured length of the window. A latency is of a request
*due* inside the window, timed from when it was due. Nothing is a median of
pieces.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

from perfbench.harness.arith import span_pairs


@dataclasses.dataclass
class Served:
    """What the client saw of one request. Times are the host's clock."""
    seq: int
    due: float
    prompt_len: int
    max_new: int
    sent: Optional[float] = None
    commits: List[tuple] = dataclasses.field(default_factory=list)
    # (time, tokens in this commit, tokens before it)
    finished: Optional[float] = None
    error: Optional[str] = None
    withdrawn: bool = False

    @property
    def n_out(self) -> int:
        return sum(n for _, n, _ in self.commits)

    @property
    def first(self) -> Optional[float]:
        return self.commits[0][0] if self.commits else None

    @property
    def last(self) -> Optional[float]:
        return self.commits[-1][0] if self.commits else None


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default), q in 0..100."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def due_in(log: List[Served], t0: float, t1: float) -> List[Served]:
    return [r for r in log if t0 <= r.due < t1 and not r.withdrawn]


def judged(log: List[Served], t0: float, t1: float,
           count: str = "due") -> List[Served]:
    """The requests a window answers for. An open loop's are those due
    inside it (`count` "due"): each is waited for after the close. A closed
    loop's are those that ended inside it ("finished"): its callers wait in
    a queue a window long by design, so what is due late in the window has
    no token at the close and is withdrawn when the callers stop."""
    if count == "due":
        return due_in(log, t0, t1)
    if count != "finished":
        raise ValueError(f"unknown count {count!r}")
    return [r for r in log if not r.withdrawn and (
        (r.finished is not None and t0 < r.finished <= t1)
        or (r.error is not None and t0 <= r.due < t1))]


def tokens_in(log: List[Served], t0: float, t1: float) -> float:
    """Output tokens produced in (t0, t1], whoever's they are. The client
    sees a request's tokens a scheduling round at a time: the tokens of a
    commit were produced since that request's commit before it, and count
    by the share of that interval that lies inside the window. (Counted
    whole at their commit, a window of a hundred rounds reads a whole round
    more or less by where its edges fall between two commits: steps of 1%,
    which is what the flood's runs showed.) A request's first commit has
    none before it and counts whole, at its own time."""
    total = 0.0
    for r in log:
        prev = None
        for t, n, _ in r.commits:
            if prev is None or t <= prev:
                total += n if t0 < t <= t1 else 0
            else:
                inside = min(t, t1) - max(prev, t0)
                if inside > 0:
                    total += n * inside / (t - prev)
            prev = t
    return total


def ttft_ms(r: Served) -> float:
    return (r.first - r.due) * 1e3


def tpot_ms(r: Served) -> Optional[float]:
    if r.n_out < 2:
        return None
    return (r.last - r.first) * 1e3 / (r.n_out - 1)


def failed(r: Served) -> bool:
    """A request due in the window fails if it errored, has no first token,
    or is not finished, once the drain limit has passed."""
    return (r.error is not None or r.first is None or r.finished is None
            or r.n_out != r.max_new)


def serving_summary(log: List[Served], t0: float, t1: float,
                    count: str = "due") -> Dict:
    """Everything the end-to-end metrics and the client-side per-layer
    metrics are read from."""
    due = judged(log, t0, t1, count)
    ok = [r for r in due if not failed(r)]
    ttft = [ttft_ms(r) for r in ok]
    tpot = [x for x in (tpot_ms(r) for r in ok) if x is not None]
    tokens = tokens_in(log, t0, t1)
    late = [(r.sent - r.due) * 1e3 for r in log
            if t0 <= r.due < t1 and r.sent is not None]
    return {
        "window_s": t1 - t0,
        "attempted": len(due),
        "failed": sum(failed(r) for r in due),
        "tokens": tokens,
        "tokens_per_s": tokens / (t1 - t0),
        "ttft_ms": ttft, "tpot_ms": tpot, "lateness_ms": late,
    }


def decode_and_prefill_work(log: List[Served], t0: float, t1: float):
    """Token work the window saw, for the model-FLOPs shares: decode tokens
    committed in (t0, t1] with the live cache rows they attended, and the
    prompt tokens (with their causal pairs) of requests whose first token
    came in (t0, t1]. The first token of a request is prefill's."""
    dec_tokens = dec_rows = pre_tokens = pre_pairs = 0
    for r in log:
        for i, (t, n, before) in enumerate(r.commits):
            if not t0 < t <= t1:
                continue
            if i == 0:
                pre_tokens += r.prompt_len
                pre_pairs += r.prompt_len * (r.prompt_len + 1) // 2
                n, before = n - 1, before + 1
            if n > 0:
                # the token at output index j is produced from a cache of
                # prompt_len + j rows (the row just written included)
                dec_tokens += n
                dec_rows += span_pairs(r.prompt_len + before - 1, n)
    return {"decode_tokens": dec_tokens, "decode_rows": dec_rows,
            "prefill_tokens": pre_tokens, "prefill_pairs": pre_pairs}
