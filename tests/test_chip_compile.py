"""Compile rehearsals for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode accepts (a block tiling that
does not match XLA's layout, too much VMEM, a program that does not fit
HBM), so the kernels of the main path and the serve tick are compiled
here for a ``v5e:2x2`` topology at the widths the chip smoke runs. A
compile that passes is not a chip run: nothing executes.

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU library, and every test
worker imports this file.
"""
import functools
import importlib.util
import os
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

_SMOKE = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"


def _smoke():
    """chip_smoke.py's module (its deployment sizes are what we compile)."""
    spec = importlib.util.spec_from_file_location("chip_smoke", _SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # such compiles are written to a persistent cache but cannot be read
    # back without a chip; keep the cache out of the way
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _mosaic(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("T,V,W,C", [
    (32, 5, 3, 2),
    (100, 3, 8, 3),
    (512, 5, 16, 8),
    (4096, 5, 64, 10),       # the chip smoke's Dawid-Skene width
])
def test_ds_estep_compiles(one_chip, T, V, W, C):
    from repro.kernels.ds_estep import ds_estep
    R = W * C + 1
    c = jax.jit(ds_estep).lower(
        jax.ShapeDtypeStruct((R, C), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((T, V), jnp.int32, sharding=one_chip)).compile()
    assert _mosaic(c)


@pytest.mark.parametrize("B,T,V,W,C", [
    (4, 4096, 5, 64, 10),    # dawid_skene_batch in the chip smoke
    (8, 256, 3, 129, 2),     # the serve tick's refresh: 8 shards x window
    (8, 256, 3, 129, 200),   # the same at cub200's 200 classes
])
def test_ds_estep_vmapped_compiles(one_chip, B, T, V, W, C):
    from repro.kernels.ds_estep import ds_estep
    R = W * C + 1
    c = jax.jit(jax.vmap(ds_estep)).lower(
        jax.ShapeDtypeStruct((B, R, C), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((B, T, V), jnp.int32, sharding=one_chip)
    ).compile()
    assert _mosaic(c)


@pytest.mark.parametrize("N,C,dtype", [
    (512, 64, jnp.float32),
    (1024, 48, jnp.float32),
    (777, 17, jnp.float32),
    (1024, 128, jnp.float32),   # MIN_KERNEL_CLASSES: the learner's switch
    (300, 1000, jnp.bfloat16),
])
def test_entropy_scores_compiles(one_chip, N, C, dtype):
    from repro.kernels.uncertainty import entropy_scores
    c = jax.jit(entropy_scores).lower(
        jax.ShapeDtypeStruct((N, C), dtype, sharding=one_chip)).compile()
    assert _mosaic(c)


def test_entropy_scores_vmapped_compiles(one_chip):
    from repro.kernels.uncertainty import entropy_scores
    c = jax.jit(jax.vmap(entropy_scores)).lower(
        jax.ShapeDtypeStruct((4, 300, 128), jnp.float32, sharding=one_chip)
    ).compile()
    assert _mosaic(c)


# ---------------------------------------------------------------------------
# the serve tick
# ---------------------------------------------------------------------------

def _serve_cfg(name, overrides):
    from repro.scenarios import get_scenario
    from repro.scenarios.compile import to_serve_config
    return to_serve_config(get_scenario(name, overrides))


def _tick_args(cfg, sharding):
    from repro.labelstream.router import serve_init
    state = jax.eval_shape(functools.partial(serve_init, cfg, 0))
    inj = jax.ShapeDtypeStruct((2, cfg.n_shards), jnp.int32,
                               sharding=sharding)
    return _sds(state, sharding), inj


def test_serve_tick_compiles_at_deployment_size(one_chip):
    from repro.labelstream.router import _serve_tick_jit
    sm = _smoke()
    cfg = _serve_cfg(sm.SERVE_SCENARIO, sm.SERVE_OVERRIDES)
    state, inj = _tick_args(cfg, one_chip)
    c = _serve_tick_jit.lower(cfg, state, inj, None, None, None).compile()
    mem = c.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_serve_tick_refresh_selects_the_kernel_on_tpu(one_chip,
                                                      monkeypatch):
    """With the offline DS refresh on, the tick's E-step is the Mosaic
    kernel when the backend is a TPU (steered here: the described chip
    is not the default backend)."""
    from repro.labelstream import aggregate
    from repro.labelstream.router import _serve_tick_jit
    sm = _smoke()
    monkeypatch.setattr(aggregate, "estep_mode", lambda: (True, False))
    cfg = _serve_cfg(sm.SERVE_SCENARIO, dict(
        sm.SERVE_OVERRIDES, **{"policy.learner.refresh_every": 8}))
    state, inj = _tick_args(cfg, one_chip)
    c = _serve_tick_jit.lower(cfg, state, inj, None, None, None).compile()
    assert _mosaic(c)


def test_serve_tick_compiles_for_cub200(one_chip, monkeypatch):
    """The 200-class deployment of ``chipbench/configs/cub200.json``, its
    refresh on: the E-step is the Mosaic kernel and the tick's arguments
    and temporaries fit one chip's memory."""
    import json

    from repro.labelstream import aggregate
    from repro.labelstream.router import _serve_tick_jit
    cub = json.loads((_SMOKE.parent / "chipbench" / "configs"
                      / "cub200.json").read_text())
    monkeypatch.setattr(aggregate, "estep_mode", lambda: (True, False))
    cfg = _serve_cfg(cub["scenario"], cub["overrides"])
    assert cfg.n_classes == 200 and cfg.refresh_every > 0
    state, inj = _tick_args(cfg, one_chip)
    c = _serve_tick_jit.lower(cfg, state, inj, None, None, None).compile()
    assert _mosaic(c)
    mem = c.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


N_CHIPS = 4


@pytest.fixture
def four_chip_mesh(topo, monkeypatch):
    """A ("shard",) mesh over four described chips, handed to the router
    in place of the attached devices."""
    from repro.launch import mesh as mesh_mod
    mesh = jax.sharding.Mesh(np.asarray(topo.devices[:N_CHIPS]), ("shard",))
    monkeypatch.setattr(mesh_mod, "make_stream_mesh", lambda n: mesh)
    return mesh


def test_sharded_serve_tick_compiles_on_four_chips(four_chip_mesh):
    from repro.labelstream import router
    sm = _smoke()
    cfg = _serve_cfg(sm.SHARDED_SCENARIO, {"sharding.n_devices": N_CHIPS})
    fn = router._serve_tick_sharded_jit.__wrapped__(cfg)
    state = jax.eval_shape(functools.partial(router.serve_init, cfg, 0))
    shard = NamedSharding(four_chip_mesh, PartitionSpec("shard"))
    rep = NamedSharding(four_chip_mesh, PartitionSpec())
    state = {k: _sds(v, shard if k in router._SERVE_SHARDED_KEYS else rep)
             for k, v in state.items()}
    inj = jax.ShapeDtypeStruct(
        (2, cfg.n_shards), jnp.int32,
        sharding=NamedSharding(four_chip_mesh, PartitionSpec(None, "shard")))
    c = fn.lower(state, inj, None, None).compile()
    assert "all-gather" in c.as_text()


def test_sharded_run_stream_compiles_on_four_chips(four_chip_mesh):
    from repro.labelstream import router
    from repro.scenarios import get_scenario
    from repro.scenarios.compile import to_stream_config
    sm = _smoke()
    cfg = to_stream_config(get_scenario(
        sm.SHARDED_SCENARIO, {"sharding.n_devices": N_CHIPS}))
    fn = router._run_sharded_jit.__wrapped__(cfg, 60)
    rep = NamedSharding(four_chip_mesh, PartitionSpec())
    c = fn.lower(jax.ShapeDtypeStruct((2, 2), jnp.uint32, sharding=rep),
                 jax.ShapeDtypeStruct((), jnp.float32, sharding=rep),
                 jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)
                 ).compile()
    assert "all-gather" in c.as_text()
