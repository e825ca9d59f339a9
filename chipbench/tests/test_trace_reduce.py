"""The trace reduction against hand-computed numbers on a small trace.

Device 0: ops [0,100] [50,150]* [300,350] [400,500]* [450,470] (*
collective); device 1: one op [0,200]; the non-core plane is not a chip.
Busy: device 0 covers [0,150] [300,350] [400,500] = 300 ns, device 1
200 ns, mean 250 ns of a 1000 ns window: idle 75%. Collectives on device
0 cover 200 ns, of which [100,150] [400,450] [470,500] = 130 ns have no
other op beside them. The gaps [150,300] and [350,400] fall under the
host spans ``absorb`` and ``inject_plan``.
"""
import json
import pathlib

import pytest

import trace_reduce

DATA = pathlib.Path(__file__).resolve().parents[1] / "testdata"


@pytest.fixture()
def tr():
    return json.loads((DATA / "trace_small.json").read_text())


def test_busy_idle_and_devices(tr):
    r = trace_reduce.reduce(tr, tr["window_s"])
    assert r["n_devices"] == 2
    assert r["busy_s"] == pytest.approx(250e-9)
    assert r["idle_share"] == pytest.approx(0.75)
    assert r["busiest"]["busy_s"] == pytest.approx(300e-9)


def test_collectives_and_exposed_part(tr):
    b = trace_reduce.reduce(tr, tr["window_s"])["busiest"]
    assert b["collective_s"] == pytest.approx(200e-9)
    assert b["collective_exposed_s"] == pytest.approx(130e-9)


def test_op_times_and_gaps(tr):
    r = trace_reduce.reduce(tr, tr["window_s"])
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx(100e-9)
    assert ops["fusion.9"] == pytest.approx(200e-9)
    assert "host-transfer" not in ops
    assert r["module_time"]["jit__serve_tick_jit"] == pytest.approx(500e-9)
    assert [g[0] for g in r["idle_gaps"]] == ["absorb", "inject_plan"]
    assert [g[1] for g in r["idle_gaps"]] == pytest.approx([150e-9, 50e-9])


@pytest.mark.parametrize("a,b,want", [
    ([[0, 10]], [[2, 3], [5, 12]], [[0, 2], [3, 5]]),
    ([[0, 10], [20, 30]], [[5, 25]], [[0, 5], [25, 30]]),
    ([[0, 10]], [], [[0, 10]]),
])
def test_subtract(a, b, want):
    assert trace_reduce.subtract(a, b) == want


def test_no_device_plane_gives_nothing():
    assert trace_reduce.reduce({"planes": []}, 1.0) is None


def test_busy_over_the_window_is_an_error(tr):
    # 250 ns busy does not fit into a 200 ns window
    with pytest.raises(ValueError):
        trace_reduce.reduce(tr, 0.2 * tr["window_s"])
