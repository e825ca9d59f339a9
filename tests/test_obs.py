"""Observability layer (repro.obs): in-loop trace buffers, latency-source
decomposition, artifact export and the text report.

The load-bearing property is BIT PARITY: enabling tracing must not change
a single bit of any pre-existing engine output (the buffers record only
deterministic functions of state the engines already compute and consume
no extra randomness), and trace=None must compile the exact historical
program. tests/test_sharding.py pins the same property on the forced-
8-device sharded tick.
"""
import json

import numpy as np
import pytest

from repro.obs.trace import PHASES, EventsTrace, TraceConfig
from repro.scenarios import TraceSpec, get_scenario, run


def _assert_subtree_equal(ref, traced, path=""):
    """Every key of ``ref`` must exist in ``traced`` with identical bits."""
    if isinstance(ref, dict):
        for k in ref:
            assert k in traced, f"missing key {path}/{k}"
            _assert_subtree_equal(ref[k], traced[k], f"{path}/{k}")
    else:
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(traced),
                                      err_msg=path or "<root>")


# --------------------------------------------------------------------------
# shared runs (module scope: each engine compiles once)
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def stream_pair():
    base = run(get_scenario("heterogeneous_pool"), engine="stream",
               horizon=80, n_reps=2, seed=0)
    traced = run(get_scenario("heterogeneous_pool",
                              {"trace.enabled": True}),
                 engine="stream", horizon=80, n_reps=2, seed=0)
    return base, traced


@pytest.fixture(scope="module")
def simfast_pair():
    base = run(get_scenario("smallR1"), engine="simfast", n_reps=3, seed=0)
    traced = run(get_scenario("smallR1", {"trace.enabled": True}),
                 engine="simfast", n_reps=3, seed=0)
    return base, traced


@pytest.fixture(scope="module")
def events_pair():
    base = run(get_scenario("smallR1"), engine="events", n_reps=2, seed=0)
    traced = run(get_scenario("smallR1", {"trace.enabled": True}),
                 engine="events", n_reps=2, seed=0)
    return base, traced


# --------------------------------------------------------------------------
# bit parity: tracing observes, never perturbs
# --------------------------------------------------------------------------

def test_stream_trace_parity_bitwise(stream_pair):
    base, traced = stream_pair
    _assert_subtree_equal(base["raw"], traced["raw"])
    # and the traced run actually produced the new outputs
    for pk in PHASES:
        assert "ph_" + pk in traced["raw"]
        assert "ps_" + pk in traced["raw"]
    for k in ("votes", "busy_workers", "idle_workers", "dropped",
              "stolen", "donated"):
        assert k in traced["raw"]["series"]


def test_stream_config_trace_none_is_default():
    from repro.scenarios.compile import to_stream_config
    cfg = to_stream_config(get_scenario("heterogeneous_pool"))
    assert cfg.trace is None
    cfg_t = to_stream_config(get_scenario("heterogeneous_pool",
                                          {"trace.enabled": True}))
    assert cfg_t.trace == TraceConfig()
    # distinct static configs -> distinct compile cache entries
    assert hash(cfg) != hash(cfg_t)


def test_stream_phase_decomposition_is_exact(stream_pair):
    """backlog_wait + window_wait + work_time == time-in-system, exactly:
    each finalized task's dt-granular phase split accounts for every tick
    it spent in the system (finalize_lag overlaps the tail and is NOT part
    of the identity)."""
    _, traced = stream_pair
    raw = traced["raw"]
    s = sum(float(np.asarray(raw["ps_" + pk]).sum())
            for pk in ("backlog_wait", "window_wait", "work_time"))
    tis = float(np.asarray(raw["sum_tis"]).sum())
    assert abs(s - tis) <= 1e-3 * max(tis, 1.0), (s, tis)


def test_stream_summary_reports_phases_and_saturation(stream_pair):
    _, traced = stream_pair
    m = traced["metrics"]
    assert isinstance(m["hist_saturated"], bool)
    assert set(m["phases"]) == set(PHASES)
    for pk in PHASES:
        assert set(m["phases"][pk]) == {"mean", "p50", "p95",
                                        "hist_saturated"}
        assert m["phases"][pk]["mean"] >= 0.0


def test_hist_saturated_flags_clipped_histogram():
    """A 2-bin 1-second histogram clips everything into the top bin: the
    flag must fire and the top-bin percentile must report inf."""
    res = run(get_scenario("heterogeneous_pool",
                           {"trace.enabled": True, "engine.tis_bins": 2,
                            "engine.tis_bin_s": 1.0}),
              engine="stream", horizon=80, n_reps=1, seed=0)
    assert res["metrics"]["hist_saturated"] is True
    assert res["metrics"]["p50_tis"] == float("inf")


def test_simfast_trace_parity_and_series(simfast_pair):
    base, traced = simfast_pair
    _assert_subtree_equal(base["raw"], traced["raw"])
    raw = traced["raw"]
    n_batches = raw["trace_ticks"].shape[-1]
    for k in ("trace_ticks", "trace_votes", "trace_done", "trace_assigned",
              "trace_dups", "trace_churned", "trace_evicted",
              "trace_batch_end"):
        assert np.asarray(raw[k]).shape == (3, n_batches), k
    # conservation: per-batch finalizations sum to the done count
    assert float(np.asarray(raw["trace_done"]).sum()) \
        == float(np.asarray(raw["done"]).sum())
    # batch end times are nondecreasing within each replication
    ends = np.asarray(raw["trace_batch_end"])
    assert (np.diff(ends, axis=-1) >= 0).all()


def test_events_trace_parity_and_recorder(events_pair):
    base, traced = events_pair
    for rb, rt in zip(base["raw"], traced["raw"]):
        assert rb.total_time == rt.total_time
        assert rb.task_latencies == rt.task_latencies
        assert rb.accuracy == rt.accuracy
    rec = traced["events_trace"]
    assert isinstance(rec, EventsTrace)
    # both replications recorded: n_tasks = n_reps * scenario n_tasks
    spec = get_scenario("smallR1")
    assert len(rec.tasks) == 2 * spec.n_tasks
    for t in rec.tasks:
        assert t["window_wait"] == 0.0 and t["finalize_lag"] == 0.0
        assert t["backlog_wait"] >= 0.0 and t["work_time"] >= 0.0
        # phase split reconstructs the task latency exactly
        assert (t["backlog_wait"] + t["work_time"]) == pytest.approx(
            t["completed_at"] - t["created_at"])
    hists = rec.phase_hists(8.0, 16)
    assert set(hists) == set(PHASES)
    assert sum(hists["work_time"]["hist"]) == len(rec.tasks)


# --------------------------------------------------------------------------
# artifact: golden schema, roundtrip, report rendering
# --------------------------------------------------------------------------

def _roundtrip(res, tmp_path, name):
    from repro.obs.export import read_trace, write_trace
    path = write_trace(res["trace"], directory=str(tmp_path), name=name)
    return read_trace(path), path


@pytest.mark.parametrize("pair,kinds", [
    ("stream_pair", {"phases", "series", "counters", "summary"}),
    ("simfast_pair", {"series", "counters", "summary"}),
    ("events_pair", {"phases", "series", "counters", "summary"}),
])
def test_trace_artifact_golden_schema(pair, kinds, tmp_path, request):
    _, traced = request.getfixturevalue(pair)
    assert "trace" in traced
    doc, path = _roundtrip(traced, tmp_path, pair)
    hdr = doc["header"]
    assert hdr["schema_version"] == 1
    assert hdr["engine"] == traced["engine"]
    assert kinds <= set(doc)
    for ln in doc.get("phases", []):
        assert ln["phase"] in PHASES
        assert len(ln["hist"]) > 0 and ln["bin_s"] > 0
    for ln in doc["series"]:
        assert ln["axis"] in ("tick", "batch")
        assert ln["reduce"] in ("sum", "mean")
        assert isinstance(ln["values"], list)
    # artifact is strict JSONL: every line parses standalone
    with open(path) as f:
        for raw_line in f:
            json.loads(raw_line)


def test_stream_phase_hist_matches_engine_bins(stream_pair, tmp_path):
    _, traced = stream_pair
    doc, _ = _roundtrip(traced, tmp_path, "bins")
    cfg = traced["config"]
    for ln in doc["phases"]:
        assert len(ln["hist"]) == cfg.tis_bins


def test_read_trace_rejects_bad_schema(tmp_path):
    p = tmp_path / "TRACE_bad.jsonl"
    p.write_text(json.dumps({"kind": "header", "schema_version": 99}) + "\n")
    from repro.obs.export import read_trace
    with pytest.raises(ValueError, match="schema_version"):
        read_trace(str(p))
    p2 = tmp_path / "TRACE_worse.jsonl"
    p2.write_text(json.dumps({"kind": "series"}) + "\n")
    with pytest.raises(ValueError, match="header"):
        read_trace(str(p2))


def test_report_renders_phase_table_and_sparklines(stream_pair, tmp_path):
    from repro.obs.report import BARS, render
    _, traced = stream_pair
    doc, _ = _roundtrip(traced, tmp_path, "render")
    txt = render(doc)
    for pk in PHASES:
        assert pk in txt
    assert "latency sources" in txt
    assert any(ch in txt for ch in BARS)
    assert "counters" in txt and "summary metrics" in txt


def test_report_cli_multi_artifact(stream_pair, simfast_pair, tmp_path,
                                   capsys):
    from repro.obs.report import main
    _, p1 = _roundtrip(stream_pair[1], tmp_path, "a")
    _, p2 = _roundtrip(simfast_pair[1], tmp_path, "b")
    assert main([p1, p2]) == 0
    out = capsys.readouterr().out
    assert out.count("== trace:") == 2
    assert "engine=stream" in out and "engine=simfast" in out


def test_export_cli_end_to_end(tmp_path, capsys):
    from repro.obs.export import main, read_trace
    out_path = str(tmp_path / "TRACE_cli.jsonl")
    rc = main(["heterogeneous_pool", "--horizon", "40", "--n-reps", "1",
               "--out", out_path])
    assert rc == 0
    doc = read_trace(out_path)
    assert doc["header"]["engine"] == "stream"
    assert {"phases", "series", "counters", "summary", "wallclock"} \
        <= set(doc)
    # the CLI runs cold+warm, so the wallclock section can split compile
    entries = doc["wallclock"][0]["entries"]
    mine = [e for e in entries if e["name"].startswith(
        "run[heterogeneous_pool")]
    assert mine and mine[0]["calls"] >= 2
    assert mine[0]["compile_s"] is not None


# --------------------------------------------------------------------------
# spec + timing plumbing
# --------------------------------------------------------------------------

def test_trace_spec_validation():
    with pytest.raises(ValueError, match="phases/per_tick"):
        TraceSpec(enabled=True, phases=False, per_tick=False)
    with pytest.raises(ValueError, match="phases/per_tick"):
        TraceConfig(phases=False, per_tick=False)
    # disabled spec may carry any flags (they are ignored)
    TraceSpec(enabled=False, phases=False, per_tick=False)


def test_trace_config_partial_modes():
    """phases-only and per_tick-only both lower and run."""
    res = run(get_scenario("heterogeneous_pool",
                           {"trace.enabled": True, "trace.per_tick": False}),
              engine="stream", horizon=40, n_reps=1, seed=0)
    assert "ph_backlog_wait" in res["raw"]
    assert "votes" not in res["raw"]["series"]
    res2 = run(get_scenario("heterogeneous_pool",
                            {"trace.enabled": True, "trace.phases": False}),
               engine="stream", horizon=40, n_reps=1, seed=0)
    assert "ph_backlog_wait" not in res2["raw"]
    assert "votes" in res2["raw"]["series"]


def test_timing_registry_cold_warm_split():
    from repro.obs import timing
    timing.clear()
    timing.record("f", 1.0)
    timing.record("f", 0.25)
    timing.record("f", 0.35)
    timing.record("g", 0.5)
    s = {e["name"]: e for e in timing.summary()}
    assert s["f"]["calls"] == 3
    assert s["f"]["cold_s"] == 1.0
    assert s["f"]["warm_s"] == pytest.approx(0.3)
    assert s["f"]["compile_s"] == pytest.approx(0.7)
    assert s["g"]["warm_s"] is None and s["g"]["compile_s"] is None
    timing.clear()
    assert timing.summary() == []


def test_span_records_start_end_parent_and_self_time():
    import time

    from repro.obs import timing
    timing.clear()
    t_before = time.monotonic()
    with timing.span("outer"):
        with timing.span("inner"):
            time.sleep(0.02)
        with timing.span("inner"):
            time.sleep(0.01)
        time.sleep(0.01)
    t_after = time.monotonic()
    recs = timing.spans()
    assert [r.name for r in recs] == ["inner", "inner", "outer"]
    outer = timing.spans("outer")[0]
    inners = timing.spans("inner")
    assert outer.parent is None
    assert all(r.parent == outer.id for r in inners)
    assert t_before <= outer.start <= inners[0].start <= inners[0].end \
        <= inners[1].start <= inners[1].end <= outer.end <= t_after
    own = timing.self_time(outer, recs)
    assert own == pytest.approx(outer.seconds - inners[0].seconds
                                - inners[1].seconds)
    assert 0.01 <= own < outer.seconds
    assert timing.self_time(inners[0], recs) == inners[0].seconds
    # the per-name duration lists the registry always kept
    ent = timing.entries()
    assert ent["outer"] == [outer.seconds]
    assert ent["inner"] == [r.seconds for r in inners]
    timing.clear()
    assert timing.spans() == [] and timing.entries() == {}


def test_span_parent_follows_the_thread():
    """A span open on one thread is no parent to spans another thread
    opens meanwhile; spans that close out of order (asyncio tasks that
    interleave on one thread) leave no stale parent behind."""
    import threading

    from repro.obs import timing
    timing.clear()
    opened, done = threading.Event(), threading.Event()

    def holder():
        with timing.span("held"):
            opened.set()
            done.wait(10)
            with timing.span("held.child"):
                pass

    th = threading.Thread(target=holder)
    th.start()
    opened.wait(10)
    with timing.span("other"):
        pass
    done.set()
    th.join(10)
    assert not th.is_alive()
    by = {r.name: r for r in timing.spans()}
    assert by["other"].parent is None
    assert by["held.child"].parent == by["held"].id
    assert by["held"].start < by["other"].start < by["held"].end

    a, b = timing.span("a"), timing.span("b")
    a.__enter__(), b.__enter__()
    a.__exit__(None, None, None), b.__exit__(None, None, None)
    with timing.span("after"):
        pass
    assert timing.spans("after")[0].parent is None
    timing.clear()


def test_timeit_fills_entries_as_before():
    from repro.obs import timing
    timing.clear()
    out, dt = timing.timeit("f", lambda a, b=0: a + b, 2, b=3)
    timing.timeit("f", lambda: None)
    assert out == 5 and dt >= 0.0
    ent = timing.entries()
    assert list(ent) == ["f"] and len(ent["f"]) == 2 and ent["f"][0] == dt
    assert [s.name for s in timing.spans()] == ["f", "f"]
    assert {e["name"]: e["calls"] for e in timing.summary()} == {"f": 2}
    timing.clear()


def test_span_annotates_only_while_a_profiler_records(monkeypatch):
    """No annotation object is made while nothing records: making one per
    span slowed the served tick on the chip."""
    from repro.obs import timing

    made = []

    class Annotation:
        enabled = False

        @classmethod
        def is_enabled(cls):
            return cls.enabled

        def __init__(self, name):
            made.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(timing, "_ANNOTATION", Annotation)
    with timing.span("off"):
        pass
    Annotation.enabled = True
    with timing.span("on"):
        pass
    assert made == ["on"]
    assert [s.name for s in timing.spans()][-2:] == ["off", "on"]
    timing.clear()


def test_span_lands_on_the_host_plane_of_a_profiler_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from repro.obs import timing
    timing.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with timing.span("obs.test.span"):
            jnp.ones((8,)).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    pd = ProfileData.from_file(str(path))
    hits = [(pl.name, ev.duration_ns) for pl in pd.planes
            if pl.name.startswith("/host:") for ln in pl.lines
            for ev in ln.events if ev.name == "obs.test.span"]
    assert len(hits) == 1, hits
    sp = timing.spans("obs.test.span")[0]
    # the annotation opens before the registry's first clock read and
    # closes after its last
    assert sp.seconds <= hits[0][1] * 1e-9 <= sp.seconds + 1e-3
    timing.clear()
