"""With the offline Dawid-Skene refresh broken underneath a tiny cub200
run, ``correct`` comes out false; so does the control (the reference in
bfloat16, EM included, in the program's place). CPU, tiny sizes."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

import check
import check_ds
from test_cells_cpu import tiny_run

CELL = "cub200.poisson"


def refresh_skipped_once(skipped: list):
    """The refresh left out of the first refresh tick after the window's
    close (a tick the comparison judges); its step goes into
    ``skipped``."""
    import repro.labelstream.router as router

    def fault(real):
        def tick(cfg, state, *a, **kw):
            c = router._as_serve_config(cfg)
            k = c.refresh_every
            if router.serve_tick.check and not skipped \
                    and int(state["step"]) % k == k - 1:
                skipped.append(int(state["step"]))
                c = dataclasses.replace(c, refresh_every=0)
            return real(c, state, *a, **kw)
        return tick
    return fault


def em_one_iteration_short(real):
    """Every refresh's EM runs one iteration fewer than configured."""
    import repro.labelstream.router as router

    def tick(cfg, state, *a, **kw):
        c = router._as_serve_config(cfg)
        c = dataclasses.replace(c, refresh_iters=c.refresh_iters - 1)
        return real(c, state, *a, **kw)
    return tick


def test_refresh_skipped_once_fails(tmp_path):
    skipped = []
    c, r = tiny_run(CELL, tmp_path, seed=21, drain_s=3.0,
                    fault=refresh_skipped_once(skipped))
    assert len(skipped) == 1
    assert not check.passed(r["checks"], c["config"]), r["checks"]


def test_em_one_iteration_short_fails(tmp_path):
    c, r = tiny_run(CELL, tmp_path, seed=24, drain_s=3.0,
                    fault=em_one_iteration_short)
    assert r["checks"]["checked_refreshes"] > 0
    assert not check.passed(r["checks"], c["config"]), r["checks"]


def test_estep_dropping_the_last_vote_fails(tmp_path, monkeypatch):
    """The E-step leaves out each task's last vote."""
    from repro.labelstream import aggregate
    real = aggregate._estep

    def estep(log_conf_rows, idx, *a):
        null = log_conf_rows.shape[0]
        last = (idx < null).sum(-1, keepdims=True) - 1
        cols = jnp.arange(idx.shape[-1])
        return real(log_conf_rows, jnp.where(cols == last, null, idx), *a)

    monkeypatch.setattr(aggregate, "_estep", estep)
    jax.clear_caches()          # retrace the tick with the broken E-step
    try:
        c, r = tiny_run(CELL, tmp_path, seed=22)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not check.passed(r["checks"], c["config"]), r["checks"]


def test_sound_run_passes_and_control_fails(tmp_path):
    c, r = tiny_run(CELL, tmp_path, seed=23)
    cfg = c["config"]
    assert check.passed(r["checks"], cfg), r["checks"]
    assert r["checks"]["checked_refreshes"] >= cfg["limits"][
        "checked_refreshes"]
    ctl = check_ds.check_ticks(cfg["reference"], r["checked"],
                               cfg["decision_eps"], control="bfloat16")
    assert ctl["checked_refreshes"] == r["checks"]["checked_refreshes"]
    assert not check.passed(dict(r["checks"], **{
        k: v for k, v in ctl.items() if k in cfg["limits"]}), cfg)
    assert ctl["logpost_err"] > 10 * r["checks"]["logpost_err"]


def test_a_refused_tick_stops_the_run_with_its_error(tmp_path, monkeypatch):
    """A tick that raises (as a program the compiler refuses does) ends
    the run at warm-up with the tick's own error."""
    import repro.labelstream.router as router

    class Refused(Exception):
        pass

    def tick(*a, **kw):
        raise Refused("the tick could not be built")

    monkeypatch.setattr(router, "serve_tick", tick)
    with pytest.raises(Refused):
        tiny_run(CELL, tmp_path, seed=25)
