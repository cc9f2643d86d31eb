"""The end-to-end metrics, each from the host's clock over the whole
window. A metric that the run's kind does not have reads None."""
from __future__ import annotations

import re

from perfbench.harness import stats

_PCT = re.compile(r"^(ttft|tpot)_p(\d+)_ms$")


def read(name: str, run: dict):
    summary = run["summary"]
    if name == "setup_s":
        return run["ctx"]["setup_s"]
    if name == "serve_tokens_per_s" and run["kind"] == "serving":
        return summary["tokens_per_s"]
    if name == "train_tokens_per_s" and run["kind"] == "training":
        return summary["tokens_per_s"]
    m = _PCT.match(name)
    if m and run["kind"] == "serving":
        values = summary[f"{m.group(1)}_ms"]
        return stats.percentile(values, int(m.group(2))) if values else None
    return None
