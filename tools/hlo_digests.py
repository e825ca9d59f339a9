"""Digests of the router's lowered programs, for showing that a refactor
leaves every compiled program as it was.

Lowers (does not compile) each ``labelstream.router`` program of the
checkout it runs in and prints a short sha256 of its StableHLO text with
source locations stripped: the serve tick and ``run_stream``'s program of
every registry stream scenario (plain, traced, and with stealing where the
admission allows it), the sweep, votes-cap and grid programs, and the
served tick of the benchmark's deployments (``chipbench/configs``, built
as ``chipbench/served.py`` builds them, with abstract state and bank at
deployment size). The sharded scenarios lower through both ``shard_map``
wrappers, which needs four host devices.

Run it from the root of each checkout and compare::

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        PYTHONPATH=src python tools/hlo_digests.py --out before.json
    # ... in the other checkout:
    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        PYTHONPATH=src python /path/to/tools/hlo_digests.py --against before.json

``--against`` exits 1 when a program's digest differs or a program is
missing on one side. ``--match`` keeps only programs whose name contains
the string (``deploy:`` for the three served deployments). ``--text DIR``
also writes each stripped text, for diffing.
"""
import argparse
import dataclasses
import functools
import hashlib
import json
import pathlib
import re
import sys

import jax
import jax.numpy as jnp

from repro.labelstream import router
from repro.obs.trace import TraceConfig
from repro.scenarios import get_scenario, list_scenarios
from repro.scenarios.compile import to_serve_config, to_stream_config

HORIZON = 12
DEPLOYMENTS = ("crowd1k", "lmtext1k", "cub200")


def strip_locations(text: str) -> str:
    text = re.sub(r"\s*loc\((?:[^()]|\([^()]*\))*\)", "", text)
    return "\n".join(line for line in text.splitlines()
                     if not line.startswith("#loc"))


def bank_shape(cfg):
    if cfg.learner.feature_kind != "lm":
        return None
    C, F = cfg.n_classes, cfg.learner.n_features
    K = cfg.learner.embed.bank_size // (2 * C)
    return jax.ShapeDtypeStruct((2, C, K, F), jnp.float32)


def lower_serve(cfg):
    state = jax.eval_shape(functools.partial(router.serve_init, cfg, 0))
    S, M = cfg.n_shards, cfg.max_arrivals_per_tick
    inj = jax.ShapeDtypeStruct((2, S), jnp.int32)
    feat = labels = None
    if cfg.learner.feature_kind == "lm":
        feat = jax.ShapeDtypeStruct((S, M, cfg.learner.n_features),
                                    jnp.float32)
        labels = jax.ShapeDtypeStruct((S, M), jnp.int32)
    if cfg.sharding.n_devices > 1:
        return router._serve_tick_sharded_jit(cfg).lower(state, inj, feat,
                                                         labels)
    return router._serve_tick_jit.lower(cfg, state, inj, feat, labels,
                                        bank_shape(cfg))


def lower_run(cfg):
    keys = jax.random.split(jax.random.key(0), 2)
    if cfg.sharding.n_devices > 1:
        return router._run_sharded_jit(cfg, HORIZON).lower(
            jax.random.key_data(keys), jnp.float32(1.0), jnp.float32(1.0))
    return router._run_jit.lower(cfg, HORIZON, keys, 3.0, jnp.float32(1.0),
                                 bank_shape(cfg))


def variants(name, cfg):
    yield name, cfg
    yield name + "+trace", dataclasses.replace(cfg, trace=TraceConfig())
    if cfg.routing.admission == "fifo":
        yield name + "+steal", dataclasses.replace(
            cfg, sharding=dataclasses.replace(cfg.sharding, steal="pressure"))


def programs():
    """(name, thunk returning a ``Lowered``) for every program."""
    for name in list_scenarios():
        try:
            base = to_stream_config(get_scenario(name))
        except Exception:  # noqa: BLE001 -- not a stream scenario
            continue
        if not isinstance(base, router.StreamConfig):
            continue
        for vname, cfg in variants(name, base):
            try:
                router._validate_stream_config(cfg)
            except ValueError:
                pass
            else:
                yield "run:" + vname, functools.partial(lower_run, cfg)
            yield "serve:" + vname, functools.partial(
                lower_serve, dataclasses.replace(cfg, serve=True))

    keys = jax.random.split(jax.random.key(0), 2)
    for name in ("stream_default", "skewed_learner_fused", "lm_stream"):
        cfg = to_stream_config(get_scenario(name))
        bank = bank_shape(cfg)
        yield "swept:" + name, functools.partial(
            router._run_swept.lower, cfg, HORIZON, keys, 3.0,
            jnp.asarray([0.5, 1.0], jnp.float32), bank)
        capcfg = dataclasses.replace(
            cfg, policy=dataclasses.replace(cfg.policy, votes_cap=5))
        yield "capswept:" + name, functools.partial(
            router._run_capswept.lower, capcfg, HORIZON, keys, 3.0,
            jnp.asarray([3, 5], jnp.int32), jnp.float32(1.0), bank)
        dtypes = (jnp.float32, jnp.int32, jnp.float32, jnp.float32,
                  jnp.float32, jnp.float32)
        traced = router.StreamTraced(*[
            jnp.broadcast_to(jnp.asarray(v, d), (2,))
            for v, d in zip(router.StreamTraced(), dtypes)])
        yield "grid:" + name, functools.partial(
            router._run_grid_jit.lower, cfg, HORIZON, keys,
            jnp.float32(3.0), traced, bank)

    for dep in DEPLOYMENTS:
        conf = json.loads(pathlib.Path(
            f"chipbench/configs/{dep}.json").read_text())
        ov = dict(conf["overrides"])
        ov.update({k: 1 for k in conf.get("seed_overrides", ())})
        cfg = to_serve_config(get_scenario(conf["scenario"], ov))
        yield "deploy:" + dep, functools.partial(lower_serve, cfg)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", help="write the digests here as JSON")
    ap.add_argument("--against", help="JSON of another checkout's digests")
    ap.add_argument("--match", default="", help="name substring filter")
    ap.add_argument("--text", help="directory for the stripped texts")
    args = ap.parse_args(argv)

    digests = {}
    for name, lower in programs():
        if args.match not in name:
            continue
        try:
            text = strip_locations(lower().as_text())
        except Exception as e:  # noqa: BLE001 -- reported per program
            digests[name] = f"ERROR {type(e).__name__}: {str(e)[:120]}"
        else:
            digests[name] = hashlib.sha256(text.encode()).hexdigest()[:16]
            if args.text:
                d = pathlib.Path(args.text)
                d.mkdir(parents=True, exist_ok=True)
                (d / (name.replace(":", "_") + ".mlir")).write_text(text)
        print(f"{name:44s} {digests[name]}", flush=True)
    if args.out:
        pathlib.Path(args.out).write_text(
            json.dumps(digests, indent=1, sort_keys=True) + "\n")
    if not args.against:
        return 0
    other = json.loads(pathlib.Path(args.against).read_text())
    other = {k: v for k, v in other.items() if args.match in k}
    differ = sorted(k for k in set(digests) | set(other)
                    if digests.get(k) != other.get(k)
                    or digests.get(k, "").startswith("ERROR"))
    for k in differ:
        print(f"DIFFERS {k}: {other.get(k)} -> {digests.get(k)}")
    print(f"{len(digests) - len(differ)} of {len(set(digests) | set(other))}"
          " programs identical")
    return 1 if differ else 0


if __name__ == "__main__":
    sys.exit(main())
