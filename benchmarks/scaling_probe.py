"""One device-scaling probe point for the bench_labelstream scaling section.

Runs the ``stream_sharded`` registry workload at a given device count in a
FRESH process: the parent bench spawns this module with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` in the child
environment (the flag must be set before the first jax import, which a
long-lived parent that already initialized jax cannot do for itself).

Prints one JSON object on the last stdout line:

  * ``digest``        — sha1 over every output array's bytes; equal
    digests across device counts == bitwise-identical results (the
    single-device parity pin, machine-independent);
  * ``conservation_ok`` / counter totals — machine-independent;
  * ``wall_s`` / ``tasks_per_sec`` — wall-clock, machine-DEPENDENT:
    reported as info only, never regression-gated (virtual host devices
    on a small CPU runner share the same cores, so forced-device scaling
    reflects tick-machinery overheads, not real parallel speedup — the
    honest speedup measurement needs as many cores/chips as devices).
"""
from __future__ import annotations

import argparse
import hashlib
import json
import sys
import time


def probe(n_devices: int, horizon: int, reps: int, rate_scale: float,
          window: int, seed: int = 3) -> dict:
    import jax
    import numpy as np

    from repro import scenarios
    from repro.labelstream.router import run_stream
    from repro.scenarios.compile import to_stream_config

    cfg = to_stream_config(scenarios.get_scenario(
        "stream_sharded", {"window": window,
                           "sharding.n_devices": n_devices}))
    kw = dict(n_reps=reps, seed=seed, rate_scale=rate_scale)
    run_stream(cfg, horizon, **kw)                    # compile (untimed)
    t0 = time.perf_counter()
    out = run_stream(cfg, horizon, **kw)
    jax.block_until_ready(out)
    wall = time.perf_counter() - t0

    h = hashlib.sha1()
    for k in sorted(out):
        for leaf in jax.tree_util.tree_leaves(out[k]):
            h.update(np.asarray(leaf).tobytes())
    arrived = int(np.asarray(out["arrived"]).sum())
    accounted = (int(np.asarray(out["done_all"]).sum())
                 + int(np.asarray(out["dropped"]).sum())
                 + int(np.asarray(out["backlog_end"]).sum())
                 + int(np.asarray(out["in_flight_end"]).sum()))
    return {
        "devices": int(jax.device_count()),
        "platform": jax.devices()[0].platform,
        "n_devices": n_devices,
        "digest": h.hexdigest(),
        "arrived": arrived,
        "accounted": accounted,
        "conservation_ok": arrived == accounted,
        "done_all": int(np.asarray(out["done_all"]).sum()),
        "stolen": int(np.asarray(out["stolen"]).sum()),
        "wall_s": wall,
        "tasks_per_sec": arrived / max(wall, 1e-9),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--horizon", type=int, default=400)
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--rate-scale", type=float, default=10.0)
    ap.add_argument("--window", type=int, default=8)
    ap.add_argument("--seed", type=int, default=3)
    args = ap.parse_args(argv)
    json.dump(probe(args.devices, args.horizon, args.reps, args.rate_scale,
                    args.window, args.seed), sys.stdout)
    sys.stdout.write("\n")


if __name__ == "__main__":
    main()
