"""The served tick with the offline Dawid-Skene refresh at 200 classes
(cub200 at 2 shards x 8 workers, window 16), driven directly for enough
ticks to hold several refreshes, against ``reference/crowd_tick_ds.py``;
and the reference's EM against a dense textbook form of the same
equations."""
import json
import pathlib

import jax
import numpy as np
import pytest

import check_ds
import served
from reference import crowd_tick_ds

HERE = pathlib.Path(__file__).resolve().parents[1]
CUB = json.loads((HERE / "configs" / "cub200.json").read_text())
SMALL = {"pool.n_shards": 2, "pool.pool_size": 8, "window": 16,
         "backlog": 128}
SMALL_REF = {"n_shards": 2, "pool_size": 8, "window": 16, "backlog": 128}


def _record_ticks(n_ticks: int, seed: int):
    """Every tick's pre-state, injection, output and post-state."""
    from repro.labelstream.router import serve_init, serve_tick
    from repro.scenarios.compile import to_serve_config
    spec = served.server_spec(CUB, SMALL)
    served.check_semantics(spec, dict(CUB["reference"], **SMALL_REF))
    cfg = to_serve_config(spec)
    rng = np.random.default_rng(seed)
    state = serve_init(cfg, seed)
    base = np.zeros((cfg.n_shards,), np.int32)
    ticks = []
    for _ in range(n_ticks):
        n = rng.integers(0, 6, cfg.n_shards).astype(np.int32)
        pre = jax.device_get(state)
        state, out = serve_tick(cfg, state, n, base)
        ticks.append(dict(pre=pre, n_arr=n, uid_base=base.copy(), feat=None,
                          labels=None, out=jax.device_get(out),
                          post=jax.device_get(state)))
        base = base + n
    return ticks


@pytest.fixture(scope="module")
def ticks():
    return _record_ticks(24, seed=5)


def test_refresh_ticks_match_the_reference(ticks):
    ref = dict(CUB["reference"], **SMALL_REF)
    got = check_ds.check_ticks(ref, ticks, CUB["decision_eps"])
    assert got["tick_mismatch"] == 0, got["mismatch_fields"]
    assert got["checked_ticks"] == 24
    assert got["checked_refreshes"] == 6
    assert got["checked_answers"] > 0
    lim = CUB["limits"]
    for k in ("conf_err", "logpost_err", "est_err"):
        assert got[k] <= lim[k], (k, got[k])


def test_refresh_counts_the_votes_it_re_explains(ticks):
    """``refresh_votes`` (the E-step's real votes, read by
    ``ds_estep_roofline_pct``) holds, on each refresh tick, the votes its
    active tasks held before the tick and at most one more per worker."""
    ref = dict(CUB["reference"], **SMALL_REF)
    total = 0
    for t in ticks:
        got = check_ds.check_ticks(ref, [t], CUB["decision_eps"])
        win = t["pre"]["win"]
        before = int((np.minimum(win["n_votes"], ref["votes_cap"])
                      * win["active"]).sum())
        if got["checked_refreshes"]:
            workers = ref["n_shards"] * ref["pool_size"]
            assert before <= got["refresh_votes"] <= before + workers
        else:
            assert got["refresh_votes"] == 0
        total += got["refresh_votes"]
    assert total > 0
    assert check_ds.check_ticks(ref, ticks, CUB["decision_eps"])[
        "refresh_votes"] == total


def test_the_refresh_moves_the_state(ticks):
    """A refresh tick rewrites the online posteriors of the tasks with
    votes and the workers' estimates: judged against the reference with
    the refresh left out, it fails."""
    ref = dict(CUB["reference"], **SMALL_REF)
    moved = [t for t in ticks if crowd_tick_ds.refresh_step(
        ref, int(t["pre"]["step"])) and (t["pre"]["win"]["n_votes"] > 0).any()]
    assert moved
    off = check_ds.check_ticks(dict(ref, refresh_every=0), moved,
                               CUB["decision_eps"])
    assert off["checked_refreshes"] == 0
    assert off["logpost_err"] > 1.0 or off["tick_mismatch"] > 0


def _dense_em(labels, workers, mask, W, C, iters):
    """Dawid-Skene EM over the whole (W, C, C) confusion, float64."""
    conf = np.full((W, C, C), 0.2 / (C - 1))
    conf[:, np.arange(C), np.arange(C)] = 0.8          # (w, vote, true)
    for _ in range(iters):
        lc = np.log(np.clip(conf, 1e-6, 1.0))
        logp = np.full((labels.shape[0], C), -np.log(C))
        for t, v in zip(*np.nonzero(mask)):
            logp[t] += lc[workers[t, v], labels[t, v]]
        post = np.exp(logp - logp.max(-1, keepdims=True))
        post /= post.sum(-1, keepdims=True)
        counts = np.zeros((W, C, C))
        for t, v in zip(*np.nonzero(mask)):
            counts[workers[t, v], labels[t, v]] += post[t]
        tot = counts.sum(1)
        conf = (counts + 1.0 / C) / (tot[:, None, :] + 1.0)
        acc = (np.einsum("wcc->w", counts) + 1.0 / C) / (tot.sum(-1) + 1.0)
    return logp, acc


def test_sparse_em_matches_the_dense_equations():
    rng = np.random.default_rng(3)
    T, V, W, C = 40, 3, 6, 12
    truth = rng.integers(0, C, T)
    labels = np.where(rng.random((T, V)) < 0.8, truth[:, None],
                      rng.integers(0, C, (T, V))).astype(np.int32)
    workers = rng.integers(0, W, (T, V)).astype(np.int32)
    mask = rng.random((T, V)) < 0.8
    lp, acc, vpw = crowd_tick_ds.dawid_skene(labels, workers, mask, W, C, 5)
    lp_d, acc_d = _dense_em(labels, workers, mask, W, C, 5)
    np.testing.assert_allclose(lp, lp_d, rtol=1e-4, atol=1e-4)
    seen = vpw > 0
    np.testing.assert_allclose(acc[seen], acc_d[seen], atol=1e-5)
    np.testing.assert_array_equal(vpw, np.bincount(workers[mask],
                                                   minlength=W))
