"""Arithmetic of the readers of the program's own spans and per-request
counters.

The program keeps its spans in the ``repro.obs.timing`` registry
(``timing.spans()``: records with ``name``, ``start`` and ``end`` on
``time.monotonic()``, the clock of the window's ``t0``, a ``parent`` id
and an ``id``) and each request's injection time and ticks on the
server's request table (``run["reqs"]``). The readers run in the run's
process after ``served.run_cell``, so both are still there. A program
that keeps neither gives None, and the reader then reports nothing.
"""
from __future__ import annotations

import readers


def registry():
    """The process's span records, or None where the program keeps none."""
    from repro.obs import timing
    fn = getattr(timing, "spans", None)
    return None if fn is None else fn()


def in_window(records, name: str, t0: float, seconds: float) -> list:
    """The spans named ``name`` that start inside ``[t0, t0 + seconds)``."""
    return [r for r in records
            if r.name == name and t0 <= r.start < t0 + seconds]


def self_times(spans, records) -> list:
    """Each of ``spans``' duration less its children's among ``records``
    (the children of one span run one after another on its thread)."""
    kids: dict = {}
    for r in records:
        if r.parent is not None:
            kids[r.parent] = kids.get(r.parent, 0.0) + (r.end - r.start)
    return [s.end - s.start - kids.get(s.id, 0.0) for s in spans]


def mean_ms(xs):
    return 1e3 * sum(xs) / len(xs) if xs else None


def span_ms(run, name: str, records=None):
    """Mean duration in ms of the window's spans named ``name``."""
    records = registry() if records is None else records
    if records is None:
        return None
    return mean_ms([r.end - r.start for r in
                    in_window(records, name, run["t0"], run["seconds"])])


def self_ms(run, name: str, records=None):
    """Mean self time in ms of the window's spans named ``name``."""
    records = registry() if records is None else records
    if records is None:
        return None
    return mean_ms(self_times(
        in_window(records, name, run["t0"], run["seconds"]), records))


def answered_requests(run):
    """The server's records of the requests due in the window and
    answered, or None where the program keeps no injection counters."""
    rec, reqs = run["records"], run["reqs"]
    out = [reqs.get(rec["id"][i]) for i in readers._window(run)
           if readers._done(rec, i)]
    out = [r for r in out if r is not None]
    if not out or getattr(out[0], "t_inject", None) is None:
        return None
    return out


def queue_wait_ms(run):
    """Mean time from the server's parse to the request's injection."""
    reqs = answered_requests(run)
    return None if reqs is None else mean_ms(
        [r.t_inject - r.t_submit for r in reqs])


def answer_ticks(run):
    """Mean ticks from injection to answer, counting both ends."""
    reqs = answered_requests(run)
    if reqs is None:
        return None
    return sum(r.tick_answer - r.tick_inject + 1 for r in reqs) / len(reqs)
