"""The refresh cell's counts and readers against hand-computed numbers:
the E-step's work, the launch classification of a small trace, and the
three metrics read from a synthetic run record.

Trace (ns): serve-tick launches [0, 100], [200, 400], [500, 560] and
another program [600, 700]; E-step ops ``ds_estep.7`` at [220, 250]
and [300, 330] (inside the second launch, named after the kernel), and an
unrelated op at [10, 20]. So one refresh launch of 200 ns, two others of 100 and 60 ns:
``refresh_device_ms`` = (200 - 80) ns; two E-step calls, 60 ns.
"""
import collections

import pytest

import kernel_work
import loader
import served_ds
import spans

TRACE = {"planes": [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            ["fusion.3", 10, 10], ["%ds_estep.7 = (f32[8,256,256]) "
                                   "custom-call(...)", 220, 30],
            ["ds_estep.7", 300, 30]]},
        {"name": "XLA Modules", "events": [
            ["jit__serve_tick_jit(123)", 0, 100],
            ["jit__serve_tick_jit(123)", 200, 200],
            ["jit__serve_tick_jit(123)", 500, 60],
            ["jit_other(9)", 600, 100]]}]},
    {"name": "/host:CPU", "lines": []}]}

def test_estep_work_hand_count():
    # T=2 tasks, V=3 slots holding N=4 real votes, C=4 classes: 4*6 index
    # bytes, 4*4*4 row bytes, 8*2*4 output bytes; 4*4 adds and 5*2*4
    # softmax operations
    assert kernel_work.ds_estep_bytes(2, 3, 4, 4) == 152.0
    assert kernel_work.ds_estep_flops(2, 4, 4) == 56.0
    peak = dict(hbm_bytes_per_s=100.0, bf16_flops=1e6)
    assert kernel_work.roofline_s(152.0, 56.0, peak) == pytest.approx(1.52)
    peak = dict(hbm_bytes_per_s=1e6, bf16_flops=28.0)
    assert kernel_work.roofline_s(152.0, 56.0, peak) == pytest.approx(2.0)


def test_estep_work_charges_no_row_to_an_empty_slot():
    """Empty slots name the null row, which nothing needs to fetch: a call
    with no real vote moves only its indices and outputs."""
    assert kernel_work.ds_estep_bytes(2048, 3, 200, 0) == \
        4 * 2048 * 3 + 8 * 2048 * 200
    assert kernel_work.ds_estep_bytes(2048, 3, 200, 100) - \
        kernel_work.ds_estep_bytes(2048, 3, 200, 0) == 4 * 100 * 200


def test_launch_classification():
    ticks, calls, secs = served_ds.launches(TRACE)
    assert [k for k, _ in ticks] == [False, True, False]
    assert [s for _, s in ticks] == pytest.approx([100e-9, 200e-9, 60e-9])
    assert calls == 2
    assert secs == pytest.approx(60e-9)


def test_refresh_device_ms_and_roofline_readers():
    ticks, calls, secs = served_ds.launches(TRACE)
    run = dict(device_kind="TPU v5 lite", trace=dict(
        tick_launches=ticks,
        estep=dict(calls=calls, device_s=secs, tasks=2048, slots=3,
                   classes=200, votes=1500.0)))
    got = loader.reader("refresh_device_ms")(run)
    assert got == pytest.approx(1e3 * (200e-9 - 80e-9))
    need = kernel_work.ds_estep_bytes(2048, 3, 200, 1500.0) / 819e9
    got = loader.reader("ds_estep_roofline_pct")(run)
    assert got == pytest.approx(100.0 * need / 30e-9)


@pytest.mark.parametrize("trace", [None, {}, dict(tick_launches=[
    [False, 1e-4]], estep=dict(calls=0, device_s=0.0, tasks=1, slots=1,
                               classes=2, votes=1.0))])
def test_readers_give_nothing_without_their_data(trace):
    """A run of a program without the refresh or its kernel (or a run
    without a trace) reads nothing, without error."""
    run = dict(device_kind="TPU v5 lite", trace=trace)
    assert loader.reader("refresh_device_ms")(run) is None
    assert loader.reader("ds_estep_roofline_pct")(run) is None


Rec = collections.namedtuple("Rec", "name start end parent id")


def test_refresh_tick_ms_reads_the_window_spans(monkeypatch):
    """Refresh-tick spans [10.5, 10.512] and [11.0, 11.020] in the
    window [10, 12); one at 12.1 after it: mean 16 ms. A program without
    the span reads nothing."""
    recs = [Rec("serve.refresh_tick", 10.5, 10.512, None, 1),
            Rec("serve.tick", 10.5, 10.511, 1, 2),
            Rec("serve.refresh_tick", 11.0, 11.020, None, 3),
            Rec("serve.refresh_tick", 12.1, 12.2, None, 4)]
    read = loader.reader("refresh_tick_ms.open")
    run = dict(t0=10.0, seconds=2.0)
    monkeypatch.setattr(spans, "registry", lambda: recs)
    assert read(run) == pytest.approx(16.0)
    monkeypatch.setattr(spans, "registry", lambda: recs[1:2])
    assert read(run) is None
