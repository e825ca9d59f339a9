"""The span and request-counter readers against hand-computed numbers.

Window [10, 12) s. Spans (name, start, end, parent, id): tick 1 [9.9,
10.004] starts before the window; tick 2 [10.1, 10.108] holds dispatch
[10.1, 10.102]; tick 3 [11.0, 11.010] holds dispatch [11.0, 11.003] and
a grandchild [11.0, 11.001] of that dispatch; tick 4 starts at 12.0, at
the window's end. In the window: ticks 2 and 3, mean 9 ms; their self
times 6 and 7 ms (a grandchild is its parent's, not the tick's), mean
6.5 ms; dispatches mean 2.5 ms.
"""
import collections
import types

import pytest

import spans

Rec = collections.namedtuple("Rec", "name start end parent id")
RECORDS = [
    Rec("serve.tick", 9.9, 10.004, None, 1),
    Rec("serve.dispatch", 10.1, 10.102, 2, 3),
    Rec("serve.tick", 10.1, 10.108, None, 2),
    Rec("inner", 11.0, 11.001, 5, 6),
    Rec("serve.dispatch", 11.0, 11.003, 4, 5),
    Rec("serve.tick", 11.0, 11.010, None, 4),
    Rec("serve.tick", 12.0, 12.005, None, 7),
]
RUN = dict(t0=10.0, seconds=2.0)


def test_window_selection_and_self_time():
    ticks = spans.in_window(RECORDS, "serve.tick", 10.0, 2.0)
    assert [t.id for t in ticks] == [2, 4]
    assert spans.self_times(ticks, RECORDS) == pytest.approx([0.006, 0.007])
    assert spans.span_ms(RUN, "serve.tick", RECORDS) == pytest.approx(9.0)
    assert spans.self_ms(RUN, "serve.tick", RECORDS) == pytest.approx(6.5)
    assert spans.span_ms(RUN, "serve.dispatch", RECORDS) == pytest.approx(2.5)
    assert spans.span_ms(RUN, "serve.yield", RECORDS) is None


def _req(rid, **kw):
    return types.SimpleNamespace(rid=rid, **kw)


def test_request_counters_of_the_window():
    """Requests due in the window and answered count; one due after the
    close and one refused do not. A request table without injection
    counters gives nothing."""
    run = dict(seconds=2.0, records=dict(
        due=[0.5, 1.0, 1.5, 2.5], http=[200, 200, 429, 200],
        status=["done", "done", "none", "done"], answered=[0.6, 1.1, -1, 2.6],
        id=[0, 1, -1, 3]))
    run["reqs"] = {
        0: _req(0, t_submit=5.0, t_inject=5.004, tick_inject=10,
                tick_answer=14),
        1: _req(1, t_submit=6.0, t_inject=6.002, tick_inject=20,
                tick_answer=21),
        3: _req(3, t_submit=7.0, t_inject=7.5, tick_inject=30,
                tick_answer=90)}
    assert spans.queue_wait_ms(run) == pytest.approx(3.0)
    assert spans.answer_ticks(run) == pytest.approx(3.5)
    run["reqs"] = {k: _req(k, t_submit=1.0) for k in (0, 1, 3)}
    assert spans.queue_wait_ms(run) is None
    assert spans.answer_ticks(run) is None
