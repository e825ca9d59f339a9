"""Shared arithmetic of the metric readers in ``metrics/``.

Each reader takes the run's record (``served.run_cell``'s dict: the
window's length and tick count, the window's ``serve.tick`` and
``serve.embed`` host-clock durations, the generator's per-request record
and, in a traced run, the trace reduction under ``trace``) and returns a
number, or None when the run holds nothing for it to read.
"""
from __future__ import annotations

import math


def _window(run):
    """Indices of the requests due inside the window."""
    rec, w = run["records"], run["seconds"]
    return [i for i, d in enumerate(rec["due"]) if d < w]


def _done(rec, i) -> bool:
    return rec["http"][i] == 200 and rec["status"][i] == "done" \
        and rec["answered"][i] >= 0


def latencies(run):
    """Due-to-answer latency of every request due in the window; a request
    that failed counts as never answered (infinite)."""
    rec = run["records"]
    return [rec["answered"][i] - rec["due"][i] if _done(rec, i)
            else math.inf for i in _window(run)]


def percentile(xs, q):
    """Linear-interpolated percentile (numpy's default) of a list."""
    xs = sorted(xs)
    if not xs:
        return None
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    if xs[hi] == math.inf:
        return math.inf if k > lo or xs[lo] == math.inf else xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def answered_per_s(run):
    rec, w = run["records"], run["seconds"]
    n = sum(1 for i in range(len(rec["due"]))
            if _done(rec, i) and 0 <= rec["answered"][i] < w)
    return n / w


def answer_pct_s(run, q):
    return percentile(latencies(run), q)


def tick_ms(run):
    ts = run["tick_s"]
    return 1e3 * sum(ts) / len(ts) if ts else None


def embed_ms(run):
    es = run["embed_s"]
    return 1e3 * sum(es) / len(es) if es else None


def host_loop_ms(run):
    """Mean host time per tick outside the two device calls: the window's
    length over its ticks, less the mean ``serve.tick`` and the window's
    ``serve.embed`` time per tick."""
    n = run["ticks"]
    if not n or not run["tick_s"]:
        return None
    return 1e3 * (run["window_s"] / n - sum(run["tick_s"]) / len(run["tick_s"])
                  - sum(run["embed_s"]) / n)


def device_idle_pct(run):
    tr = run.get("trace")
    if not tr or tr.get("idle_share") is None:
        return None
    return 100.0 * tr["idle_share"]
