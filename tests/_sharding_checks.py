"""Forced-multi-device sharding checks, runnable two ways.

tests/test_sharding.py imports :func:`collect` directly when the current
process already sees >= 8 XLA devices (the CI multi-device leg exports
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before pytest
starts); otherwise it re-executes this file as a subprocess, where the
``__main__`` block sets the flag BEFORE the first jax import and prints
the collected report as JSON on stdout.

Everything here is a machine-independent deterministic quantity (bitwise
parity flags, conserved counters) — no timing, so the report is identical
on any host.
"""
import json
import sys

HORIZON = 300          # 60 ticks at dt=5
N_REPS = 2
N_DEV = 8


def _tree_equal(a, b):
    import jax
    import jax.numpy as jnp
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and \
        all(bool(jnp.array_equal(x, z)) for x, z in zip(la, lb))


def _common(out_a, out_b):
    keys = sorted(set(out_a) & set(out_b) - {"per_shard"})
    return ({k: out_a[k] for k in keys}, {k: out_b[k] for k in keys})


def _serve_outs(cfg, n_ticks: int):
    """``n_ticks`` serve ticks under a fixed injection schedule: each
    tick's fetched output and the pytree leaf count of its device output."""
    import jax
    import numpy as np

    from repro.labelstream.router import serve_init, serve_tick

    S = cfg.n_shards
    state, base = serve_init(cfg, seed=3), np.zeros((S,), np.int32)
    outs, n_leaves = [], set()
    for i in range(n_ticks):
        n = np.asarray([(i + s) % 3 for s in range(S)], np.int32)
        state, out = serve_tick(cfg, state, n, base)
        base = base + n
        n_leaves.add(len(jax.tree_util.tree_leaves(out)))
        outs.append(jax.device_get(out))
    return outs, n_leaves


def _serve_packed_parity(scenario: str, D: int) -> dict:
    """The sharded serve tick on ``D`` devices against one device: the
    packed output unpacks to the same keys, shapes, dtypes and bytes."""
    import numpy as np

    from repro import scenarios
    from repro.scenarios.compile import to_serve_config

    o1, l1 = _serve_outs(to_serve_config(
        scenarios.get_scenario(scenario)), 12)
    oD, lD = _serve_outs(to_serve_config(scenarios.get_scenario(
        scenario, {"sharding.n_devices": D})), 12)
    same = all(
        list(a) == list(b) and all(
            a[k].shape == b[k].shape and a[k].dtype == b[k].dtype
            and a[k].tobytes() == b[k].tobytes() for k in a)
        for a, b in zip(o1, oD))
    return dict(parity=same, one_leaf=l1 == lD == {1},
                fin_bool=all(o["fin"].dtype == np.bool_ for o in oD),
                finalized=sum(int(o["fin"].sum()) for o in oD))


def collect() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import scenarios
    from repro.labelstream.router import run_stream
    from repro.scenarios.compile import to_stream_config

    D = min(N_DEV, jax.device_count())
    report = {"devices": int(jax.device_count()), "probe_devices": int(D)}

    # ---- sharded-vs-single bit parity, default stream_sharded policy ----
    spec1 = scenarios.get_scenario("stream_sharded",
                                   {"sharding.steal": "none"})
    specD = scenarios.get_scenario(
        "stream_sharded", {"sharding.steal": "none",
                           "sharding.n_devices": D})
    out1 = run_stream(to_stream_config(spec1), HORIZON, n_reps=N_REPS, seed=3)
    outD = run_stream(to_stream_config(specD), HORIZON, n_reps=N_REPS, seed=3)
    a, b = _common(out1, outD)
    report["parity_default"] = _tree_equal(a, b)

    # ---- parity + activity with cross-shard work stealing on -----------
    # overload the service (small window, 10x offered rate) so backlogs
    # actually queue and the pressure-steal path fires every few ticks
    steal1 = scenarios.get_scenario("stream_sharded", {"window": 8})
    stealD = scenarios.get_scenario(
        "stream_sharded", {"window": 8, "sharding.n_devices": D})
    s1 = run_stream(to_stream_config(steal1), HORIZON, n_reps=N_REPS,
                    seed=3, rate_scale=10.0)
    sD = run_stream(to_stream_config(stealD), HORIZON, n_reps=N_REPS,
                    seed=3, rate_scale=10.0)
    a, b = _common(s1, sD)
    report["parity_steal"] = _tree_equal(a, b)
    report["stolen"] = int(np.asarray(sD["stolen"]).sum())
    report["donated"] = int(np.asarray(sD["donated"]).sum())

    # ---- conservation across steals: nothing created or lost ----------
    arrived = np.asarray(sD["arrived"]).sum()
    accounted = (np.asarray(sD["done_all"]).sum()
                 + np.asarray(sD["dropped"]).sum()
                 + np.asarray(sD["backlog_end"]).sum()
                 + np.asarray(sD["in_flight_end"]).sum())
    report["arrived"] = int(arrived)
    report["accounted"] = int(accounted)
    report["conservation_ok"] = bool(arrived == accounted)

    # ---- steal determinism: same seed -> bitwise-identical runs --------
    sD2 = run_stream(to_stream_config(stealD), HORIZON, n_reps=N_REPS,
                     seed=3, rate_scale=10.0)
    report["determinism_ok"] = _tree_equal(sD, sD2) and \
        _tree_equal(sD["per_shard"], sD2["per_shard"])

    # ---- trace buffers under the sharded tick --------------------------
    # (a) trace-ENABLED sharded vs unsharded: the per-phase accumulators
    # ride the same all-gather-then-reduce path as every other metric, so
    # the traced run must stay bit-identical across device counts too
    tr1 = scenarios.get_scenario("stream_sharded", {"trace.enabled": True})
    trD = scenarios.get_scenario(
        "stream_sharded", {"trace.enabled": True, "sharding.n_devices": D})
    t1 = run_stream(to_stream_config(tr1), HORIZON, n_reps=N_REPS, seed=3)
    tD = run_stream(to_stream_config(trD), HORIZON, n_reps=N_REPS, seed=3)
    a, b = _common(t1, tD)
    report["trace_parity_sharded"] = _tree_equal(a, b)

    # (b) trace-enabled vs trace=None on the SHARDED tick: tracing must
    # not perturb any pre-existing output (no extra randomness, no state
    # the untraced program reads)
    base_D = scenarios.get_scenario("stream_sharded",
                                    {"sharding.n_devices": D})
    u = run_stream(to_stream_config(base_D), HORIZON, n_reps=N_REPS, seed=3)

    def _restrict(big, ref):
        if isinstance(ref, dict):
            return {k: _restrict(big[k], ref[k]) for k in ref}
        return big

    report["trace_parity_none"] = _tree_equal(_restrict(tD, u), u)

    # ---- simfast pmap shards stay bit-identical ------------------------
    from repro.core.simfast import (FastConfig, SimScales, simulate,
                                    simulate_learning_batch, simulate_swept)
    fcfg = FastConfig(pool_size=12, n_tasks=24, n_records=24)
    sa = simulate(fcfg, 10, seed=5, shard=True)
    sb = simulate(fcfg, 10, seed=5, shard=False)
    report["simfast_parity"] = _tree_equal(sa, sb)

    scl = SimScales(mu=jnp.linspace(0.5, 2.0, 10))
    wa = simulate_swept(fcfg, 3, scl, seed=5, shard=True)
    wb = simulate_swept(fcfg, 3, scl, seed=5, shard=False)
    report["simfast_swept_parity"] = _tree_equal(wa, wb)

    rng = np.random.default_rng(0)
    X = rng.normal(size=(60, 4)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.int32)
    Xt = rng.normal(size=(30, 4)).astype(np.float32)
    yt = (Xt[:, 0] > 0).astype(np.int32)
    la = simulate_learning_batch(fcfg, X, y, Xt, yt, rounds=3, n_reps=10,
                                 seed=5, shard=True)
    lb = simulate_learning_batch(fcfg, X, y, Xt, yt, rounds=3, n_reps=10,
                                 seed=5, shard=False)
    report["simfast_learning_parity"] = _tree_equal(la, lb)

    # ---- grid engine: RAGGED class padded across the forced mesh -------
    # 10 cells on 8 devices pad to 16 (repeat-last); the pmapped class
    # batch must stay bit-identical to the pure-vmap run of the same grid
    from repro.grid import run_grid
    from repro.scenarios.spec import GridSpec
    gspec = GridSpec(
        base=scenarios.get_scenario("stream_default",
                                    {"pool.pool_size": 6, "window": 16}),
        axes=(("arrivals.rate", (0.006, 0.008, 0.010, 0.012, 0.014)),
              ("policy.redundancy.votes", (1, 3))),
        name="shardgrid")
    ga = run_grid(gspec, n_reps=2, horizon=120, shard=True, keep_raw=True)
    gb = run_grid(gspec, n_reps=2, horizon=120, shard=False, keep_raw=True)
    report["grid_n_cells"] = ga["n_cells"]
    report["grid_n_classes"] = ga["n_classes"]
    report["grid_ragged_pad_parity"] = all(
        _tree_equal({k: v for k, v in a["raw"].items() if k != "per_shard"},
                    {k: v for k, v in b["raw"].items() if k != "per_shard"})
        for a, b in zip(ga["cells"], gb["cells"]))

    # the simfast population bundle takes the same pad-to-device-multiple
    # path (10 traced points, 8 devices)
    from repro.core.simfast import PopTraced, simulate_swept_pop
    pop = PopTraced(acc_a=jnp.linspace(2.0, 8.0, 10))
    pa = simulate_swept_pop(fcfg, 3, pop, seed=5, shard=True)
    pb = simulate_swept_pop(fcfg, 3, pop, seed=5, shard=False)
    report["simfast_pop_pad_parity"] = _tree_equal(pa, pb)

    # ---- EmbeddingBank gather across the forced mesh -------------------
    # (a) the raw gather: pmapped device-parallel lookups must equal the
    # single-device vmap over the same indices (the bank is replicated —
    # a sharded gather that drifted would silently corrupt LM features)
    from repro.embed.bank import bank_gather, embedding_bank
    from repro.scenarios.compile import to_embed_config
    lm_spec = scenarios.get_scenario("lm_stream")
    ec = to_embed_config(lm_spec)
    bank = embedding_bank(ec, lm_spec.n_classes,
                          lm_spec.features.n_features,
                          lm_spec.features.class_sep,
                          lm_spec.features.hard_sep_scale)
    rngb = np.random.default_rng(9)
    u = rngb.random((D, 16)).astype(np.float32)
    tl = rngb.integers(0, lm_spec.n_classes, (D, 16)).astype(np.int32)
    df = (rngb.random((D, 16)) * 2).astype(np.float32)
    gp = jax.pmap(lambda uu, tt, dd: bank_gather(bank.feats, uu, tt, dd))(
        u, tl, df)
    gv = jax.vmap(lambda uu, tt, dd: bank_gather(bank.feats, uu, tt, dd))(
        u, tl, df)
    report["bank_gather_pmap_parity"] = _tree_equal(
        np.asarray(gp), np.asarray(gv))

    # (b) the full LM stream tick under shard_map (lm_stream has 2 pool
    # shards -> 2 devices) vs the single-device run: gathering from the
    # device-resident bank inside the sharded tick must stay bitwise
    # identical — same invariant the Gaussian path pins above
    lm1 = scenarios.get_scenario("lm_stream")
    lmD = scenarios.get_scenario(
        "lm_stream", {"sharding.n_devices": min(2, D)})
    l1 = run_stream(to_stream_config(lm1), HORIZON, n_reps=N_REPS, seed=3)
    lD = run_stream(to_stream_config(lmD), HORIZON, n_reps=N_REPS, seed=3)
    a, b = _common(l1, lD)
    report["lm_parity_sharded"] = _tree_equal(a, b)

    # ---- sharded serve tick: one packed buffer out, bitwise the single-
    # device tick, on the Gaussian and the LM path (2 devices) ----------
    for name in ("stream_sharded", "lm_stream"):
        report["serve_packed_" + name] = _serve_packed_parity(
            name, min(2, D))

    # ---- chip_smoke.py's four-chip phase, rehearsed on host devices ----
    import importlib.util
    import pathlib
    path = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    four = smoke.phase_four_chips(4, horizon=60, n_reps=2, n_ticks=8)
    report["chip_smoke_four_chips_ok"] = four["ok"]
    return report


if __name__ == "__main__":
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault(
        "XLA_FLAGS", f"--xla_force_host_platform_device_count={N_DEV}")
    json.dump(collect(), sys.stdout)
    sys.stdout.write("\n")
