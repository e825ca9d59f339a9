"""With the timed path broken underneath, a run's ``correct`` comes out
false; so does the control (the reference in bfloat16 in the program's
place). The chip check is skipped: these drive the rest of a run on the
CPU at a tiny size."""
import jax
import jax.numpy as jnp
import pytest

import check
import loader
from test_cells_cpu import CELLS, tiny_run

CFG = loader.cell(CELLS[0])["config"]


def altered_answer(real):
    """An answer altered where it is produced: finalized labels flipped."""
    def tick(cfg, state, *a, **kw):
        state, out = real(cfg, state, *a, **kw)
        out = dict(out)
        out["label"] = jnp.where(out["fin"], (out["label"] + 1)
                                 % cfg.n_classes, out["label"])
        return state, out
    return tick


def half_left_out(real):
    """Half of each tick's answers left out: odd window slots never
    report their finalization."""
    def tick(cfg, state, *a, **kw):
        state, out = real(cfg, state, *a, **kw)
        out = dict(out)
        keep = (jnp.arange(out["fin"].shape[1]) % 2) == 0
        out["fin"] = out["fin"] & keep[None, :]
        return state, out
    return tick


def state_unchanged(real):
    """A tick that returns its state unchanged."""
    def tick(cfg, state, *a, **kw):
        kept = jax.tree_util.tree_map(jnp.copy, state)
        _, out = real(cfg, state, *a, **kw)
        return kept, out
    return tick


@pytest.mark.parametrize("fault", [altered_answer, half_left_out,
                                   state_unchanged])
def test_fault_fails(fault, tmp_path):
    _, r = tiny_run(CELLS[0], tmp_path, seed=11, fault=fault, drain_s=3.0)
    assert not check.passed(r["checks"], CFG), r["checks"]


def test_sound_run_passes_and_control_fails(tmp_path):
    c, r = tiny_run(CELLS[0], tmp_path, seed=12)
    assert check.passed(r["checks"], CFG), r["checks"]
    ref = c["config"]["reference"]
    ctl = check.check_ticks(ref, r["checked"], CFG["decision_eps"],
                            control="bfloat16")
    assert ctl["checked_answers"] > 0
    assert not check.passed(dict(r["checks"], **{
        k: v for k, v in ctl.items() if k in CFG["limits"]}), CFG)
    assert ctl["conf_err"] > 10 * r["checks"]["conf_err"]


LM_CELLS = [c for c in CELLS if "encoder" in loader.cell(c)["config"]]


def learner_unchanged(real):
    """A tick that returns its learner (weights, Adam state) unchanged."""
    def tick(cfg, state, *a, **kw):
        kept = jax.tree_util.tree_map(jnp.copy, state["learn"])
        state, out = real(cfg, state, *a, **kw)
        return dict(state, learn=kept), out
    return tick


@pytest.mark.parametrize("cell", LM_CELLS)
@pytest.mark.parametrize("fault", [learner_unchanged, altered_answer,
                                   half_left_out, state_unchanged])
def test_lm_fault_fails(cell, fault, tmp_path):
    c, r = tiny_run(cell, tmp_path, seed=15, fault=fault, drain_s=3.0)
    assert not check.passed(r["checks"], c["config"]), r["checks"]


@pytest.mark.parametrize("cell", LM_CELLS)
def test_learner_half_batch_fails(cell, tmp_path, monkeypatch):
    """The learner's fit with half of its ring left out, the mean taken
    over the rest."""
    from repro.learning import linear
    real = linear.fit

    def fit(state, X, y, sw, **kw):
        return real(state, X, y, sw * (jnp.arange(sw.shape[0]) % 2), **kw)

    monkeypatch.setattr(linear, "fit", fit)
    jax.clear_caches()          # retrace the tick with the broken fit
    try:
        c, r = tiny_run(cell, tmp_path, seed=16)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert r["checks"]["learn_err"] > c["config"]["limits"]["learn_err"]
    assert not check.passed(r["checks"], c["config"])


@pytest.mark.parametrize("cell", LM_CELLS)
def test_lm_sound_run_passes_and_control_fails(cell, tmp_path):
    c, r = tiny_run(cell, tmp_path, seed=17)
    cfg = c["config"]
    assert check.passed(r["checks"], cfg), r["checks"]
    ctl = check.check_ticks(cfg["reference"], r["checked"],
                            cfg["decision_eps"], control="bfloat16")
    assert ctl["learn_err"] > 3 * r["checks"]["learn_err"]
    assert not check.passed(dict(r["checks"], **{
        k: v for k, v in ctl.items() if k in cfg["limits"]}), cfg)


@pytest.mark.parametrize("cell", LM_CELLS)
def test_altered_embedding_fails(cell, tmp_path, monkeypatch):
    """A text's features altered where the encoder produces them."""
    import repro.embed.bank as bank
    real = bank.encode

    def encode(*a, **kw):
        return real(*a, **kw) * 1.5

    monkeypatch.setattr(bank, "encode", encode)
    c, r = tiny_run(cell, tmp_path, seed=13)
    assert r["checks"]["embed_rel_err"] > 0.3
    assert not check.passed(r["checks"], c["config"])


@pytest.mark.parametrize("cell", LM_CELLS)
def test_encoder_control_fails(cell, tmp_path):
    """The encoder reference with float8 matrix products in the
    program's place."""
    c, r = tiny_run(cell, tmp_path, seed=14)
    cfg = c["config"]
    assert check.passed(r["checks"], cfg), r["checks"]
    ctl = check.check_embeddings(cfg["encoder"], r["embed_sample"],
                                 r["server_seed"],
                                 dtype=cfg["encoder"]["control_dtype"])
    assert ctl["embed_rel_err"] > 3 * r["checks"]["embed_rel_err"]
    assert not check.passed(dict(r["checks"], **ctl), cfg)
