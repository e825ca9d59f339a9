"""Device-sharded streaming tick (ShardingSpec -> shard_map router).

The multi-device invariants — sharded-vs-single bit parity, conservation
across cross-shard steals, steal determinism, pmap-sharded simfast paths —
need >= 8 XLA devices. When the current process already has them (the CI
multi-device leg forces host devices via XLA_FLAGS before pytest starts)
the checks run in-process; otherwise ``tests/_sharding_checks.py`` is
re-executed as a subprocess that sets
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before its first
jax import and reports JSON. Single-device semantics (spec validation,
mesh errors, masked votes-cap sweeps) are tested directly.
"""
import importlib.util
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

import jax

from repro import scenarios
from repro.scenarios.spec import (
    PolicySpec, PoolSpec, ScenarioSpec, ShardingSpec,
)

_CHECKS = pathlib.Path(__file__).with_name("_sharding_checks.py")


def _load_checks():
    spec = importlib.util.spec_from_file_location("_sharding_checks",
                                                  _CHECKS)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def report():
    if jax.device_count() >= 8:
        return _load_checks().collect()
    env = dict(os.environ)
    # forced host devices exist only on the CPU backend, and this process
    # may already hold an accelerator
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    root = str(_CHECKS.parent.parent)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(pathlib.Path(root) / "src"), root,
         env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    proc = subprocess.run([sys.executable, str(_CHECKS)], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


# --------------------------------------------------------------------------
# multi-device invariants (via the forced-8-device report)
# --------------------------------------------------------------------------

def test_sharded_matches_single_device_bitwise(report):
    assert report["devices"] >= 8
    assert report["parity_default"] is True


def test_sharded_steal_parity_and_activity(report):
    # stealing must actually fire on this workload AND keep bit parity
    assert report["parity_steal"] is True
    assert report["stolen"] > 0
    assert report["stolen"] == report["donated"]


def test_conservation_across_steals(report):
    assert report["conservation_ok"], \
        (report["arrived"], report["accounted"])


def test_steal_determinism_fixed_seed(report):
    assert report["determinism_ok"] is True


def test_trace_buffers_sharded_parity(report):
    """Trace-enabled runs stay bit-identical across device counts, and
    tracing must not perturb any pre-existing output of the sharded tick
    (trace=None vs TraceConfig agree on every shared key)."""
    assert report["trace_parity_sharded"] is True
    assert report["trace_parity_none"] is True


def test_simfast_pmap_paths_bit_identical(report):
    assert report["simfast_parity"] is True
    assert report["simfast_swept_parity"] is True
    assert report["simfast_learning_parity"] is True


def test_grid_ragged_class_pmap_bit_identical(report):
    """A 10-cell single-class grid on the forced 8-device mesh pads to 16
    (repeat-last) — dropping the padding must leave every cell bitwise
    equal to the unsharded vmap run, on both grid backends."""
    assert report["grid_n_cells"] == 10
    assert report["grid_n_classes"] == 1
    assert report["grid_ragged_pad_parity"] is True
    assert report["simfast_pop_pad_parity"] is True


def test_embedding_bank_sharded_gather_parity(report):
    """LM features across the mesh: the pmapped bank gather matches the
    single-device vmap bitwise, and the full lm_stream tick under
    shard_map stays bit-identical to the unsharded run."""
    assert report["bank_gather_pmap_parity"] is True
    assert report["lm_parity_sharded"] is True


@pytest.mark.parametrize("name", ["stream_sharded", "lm_stream"])
def test_sharded_serve_tick_packed_output(report, name):
    """The sharded serve tick returns one packed, replicated buffer that
    unpacks bitwise to the single-device tick's fields, ``fin`` bool."""
    r = report["serve_packed_" + name]
    assert r["parity"] is True
    assert r["one_leaf"] is True
    assert r["fin_bool"] is True


def test_chip_smoke_four_chip_phase(report):
    """chip_smoke.py --four-chips' comparison (4 devices against 1, every
    output digest equal, conservation) passes on host devices."""
    assert report["chip_smoke_four_chips_ok"] is True


# --------------------------------------------------------------------------
# spec / mesh validation (single device)
# --------------------------------------------------------------------------

def test_sharding_spec_validates():
    with pytest.raises(ValueError, match="ShardingSpec.n_devices"):
        ShardingSpec(n_devices=0)
    with pytest.raises(ValueError, match="ShardingSpec.steal"):
        ShardingSpec(steal="aggressive")
    with pytest.raises(ValueError, match="ShardingSpec.steal_max"):
        ShardingSpec(steal="pressure", steal_max=0)


def test_sharding_spec_divisibility_named_in_error():
    with pytest.raises(ValueError, match="sharding.n_devices"):
        ScenarioSpec(pool=PoolSpec(pool_size=6, n_shards=3),
                     sharding=ShardingSpec(n_devices=2))
    with pytest.raises(ValueError, match="shards_per_device"):
        ScenarioSpec(pool=PoolSpec(pool_size=8, n_shards=4),
                     sharding=ShardingSpec(n_devices=2, shards_per_device=3))


def test_steal_requires_fifo_admission():
    from repro.scenarios.spec import AdmissionSpec, LearnerSpec
    with pytest.raises(ValueError, match="sharding.steal"):
        ScenarioSpec(
            pool=PoolSpec(pool_size=8, n_shards=2),
            policy=PolicySpec(admission=AdmissionSpec(kind="uncertain"),
                              learner=LearnerSpec(enabled=True)),
            sharding=ShardingSpec(steal="pressure"))


def test_mesh_divisibility_and_device_errors():
    from repro.launch.mesh import check_stream_sharding, make_stream_mesh
    with pytest.raises(ValueError, match="does not divide"):
        check_stream_sharding(6, 4)
    check_stream_sharding(8, 4)   # fine
    need = jax.device_count() + 1
    with pytest.raises(ValueError,
                       match="xla_force_host_platform_device_count"):
        make_stream_mesh(need)


def test_run_stream_rejects_undivisible_devices():
    from repro.labelstream.router import ShardingConfig, StreamConfig, \
        run_stream
    cfg = StreamConfig(n_shards=3, pool_size=6,
                       sharding=ShardingConfig(n_devices=2))
    with pytest.raises(ValueError, match="does not divide"):
        run_stream(cfg, 10)


# --------------------------------------------------------------------------
# masked votes-cap sweep: one compilation, bit-for-bit vs per-value runs
# --------------------------------------------------------------------------

def _votes_cfg(votes):
    spec = scenarios.get_scenario(
        "stream_default", {"policy.redundancy.votes": votes})
    from repro.scenarios.compile import to_stream_config
    return to_stream_config(spec)


def test_votes_cap_sweep_bitwise_matches_per_value_runs():
    from repro.labelstream.router import run_stream, run_stream_votes_sweep
    caps = [2, 3, 5]
    swept = run_stream_votes_sweep(_votes_cfg(max(caps)), 200, caps,
                                   n_reps=2, seed=11)
    for i, c in enumerate(caps):
        one = run_stream(_votes_cfg(c), 200, n_reps=2, seed=11)
        skip = {"per_shard", "series", "warmup_t", "measured_s"}
        for k in set(one) & set(swept) - skip:
            np.testing.assert_array_equal(
                np.asarray(swept[k][i]), np.asarray(one[k]),
                err_msg=f"votes_cap={c} key={k}")
        # the per-tick series parity too (same masked program)
        import jax.tree_util as tu
        for (path, sv), (_, ov) in zip(
                tu.tree_flatten_with_path(swept["series"])[0],
                tu.tree_flatten_with_path(one["series"])[0]):
            np.testing.assert_array_equal(
                np.asarray(sv[i]), np.asarray(ov),
                err_msg=f"votes_cap={c} series{tu.keystr(path)}")


def test_votes_cap_sweep_validates_caps():
    from repro.labelstream.router import run_stream_votes_sweep
    cfg = _votes_cfg(5)
    with pytest.raises(ValueError, match="votes_cap"):
        run_stream_votes_sweep(cfg, 50, [0, 3])


def test_sweep_facade_votes_axis_vectorized():
    spec = scenarios.get_scenario("stream_default")
    grid = scenarios.sweep(spec, axis="policy.redundancy.votes",
                           values=[2, 4], engine="stream", horizon=150,
                           n_reps=2, seed=1)
    assert grid["vectorized"] is True
    assert len(grid["results"]) == 2
    # more budget can only help accuracy-side vote spend per task
    v2, v4 = (r["votes_per_task"] for r in grid["results"])
    assert v4 >= v2
