"""Drive the labeling service's main path once on a TPU and check it.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the sharded tick on four chips

One chip runs three phases in this one process:

1. the served path at deployment size: ``repro.serving.server.LabelServer``
   serving ``serve_default`` with 8 shards x 128 retained workers, a
   256-slot window and a 4096-task backlog per shard, driven over
   loopback HTTP by concurrent ``ServeClient``s (``repro.launch.serve.
   drive``). Every waited ``POST /tasks`` must be answered and ``GET
   /stats`` must report conservation;
2. the LM served path: ``lm_stream`` with the ``xlstm-125m`` encoder at
   its published widths (``embed.reduced=False``); submissions carry
   ``"text"`` and ``"label"`` and must be answered through the embed path;
3. the Pallas kernels through the program's own selection: Dawid-Skene EM
   (``aggregate.dawid_skene``/``dawid_skene_batch`` with
   ``use_kernel=None``) against the jnp E-step of ``kernels/ref.py``, and
   ``learning.linear.entropy_from_logits`` at >= 128 classes against
   ``ref.entropy_ref``. Each compiled program must hold a
   ``tpu_custom_call``: a fall-back to interpret mode or to the reference
   fails.

``--four-chips`` runs only the ``stream_sharded`` scenario's ``run_stream``
and ``serve_tick`` with ``sharding.n_devices=4`` against ``n_devices=1`` in
the same process, and requires equal digests of every output and
conservation.

The phases are functions of their sizes, so a CPU rehearsal at a tiny size
calls them directly. Run as a script, the smoke needs a TPU: on any other
platform it names the platform and exits non-zero. The last line of
standard output is ``{"ok": true, "device": {...}}``, printed only when
every phase passed.
"""
from __future__ import annotations

import argparse
import asyncio
import hashlib
import json
import os
import pathlib
import sys
import traceback

ROOT = pathlib.Path(__file__).resolve().parent

# the served path at deployment size (assumed sizes: a retainer pool of
# 1,024 workers in 8 shards, a 256-task window and a 4096-task backlog
# per shard)
SERVE_SCENARIO = "serve_default"
SERVE_OVERRIDES = {"pool.n_shards": 8, "pool.pool_size": 128,
                   "window": 256, "backlog": 4096}
# the LM feature path with the encoder at its published widths
LM_SCENARIO = "lm_stream"
LM_OVERRIDES = {"embed.reduced": False}
# the sharded-tick scenario of the four-chip comparison
SHARDED_SCENARIO = "stream_sharded"

_WORDS = ("label", "this", "review", "photo", "tweet", "is", "clearly",
          "positive", "negative", "spam", "relevant", "about", "a", "the")


def _log(tag: str, rec: dict):
    print(f"{tag}: {json.dumps(rec, default=str)}", flush=True)


def _digest(arrays) -> str:
    import numpy as np
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()[:16]


def _tick_split(rows, name="serve.tick"):
    row = next((r for r in rows if r["name"] == name), None)
    if row is None:
        return None
    return dict(calls=row["calls"], cold_s=row["cold_s"],
                warm_s=row["warm_s"])


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_serve(scenario: str, overrides: dict, *, n_clients: int,
                per_client: int, seed: int = 0, texts: bool = False,
                timeout_s: float = 600.0) -> dict:
    """Serve ``scenario`` with registry ``overrides`` and drive it with
    ``n_clients`` x ``per_client`` waited submissions. With ``texts`` each
    submission carries a seeded text and a known label (LM scenarios)."""
    import numpy as np

    from repro.launch.serve import drive
    from repro.obs import timing
    from repro.scenarios import get_scenario

    spec = get_scenario(scenario, overrides)
    submission = None
    if texts:
        rng = np.random.default_rng(seed)
        C = spec.n_classes

        def submission(i, k):
            words = rng.choice(_WORDS, size=int(rng.integers(4, 12)))
            return dict(text=" ".join(words), label=(i + k) % C)

    timing.clear()
    res = asyncio.run(drive(spec, seed=seed, n_clients=n_clients,
                            per_client=per_client, submission=submission,
                            timeout_s=timeout_s))
    st = res["stats"]
    rec = dict(scenario=scenario, overrides=overrides,
               submitted=res["submitted"], answered=res["answered"],
               conservation=st["conservation"], ticks=st["ticks"],
               p50_latency_s=st["p50_latency_s"],
               p95_latency_s=st["p95_latency_s"],
               serve_tick=_tick_split(st["timing"]))
    ok = res["ok"]
    if texts:
        rec["serve_embed"] = _tick_split(st["timing"], "serve.embed")
        ok = ok and rec["serve_embed"] is not None
    rec["ok"] = bool(ok)
    return rec


def _synthetic_votes(rng, n_tasks, n_votes, n_workers, n_classes):
    """A seeded crowd: workers of accuracy U(0.55, 0.95), uniform wrong
    labels, one vote in ten missing."""
    import numpy as np

    truth = rng.integers(0, n_classes, n_tasks)
    acc = rng.uniform(0.55, 0.95, n_workers)
    workers = rng.integers(0, n_workers, (n_tasks, n_votes))
    right = rng.random((n_tasks, n_votes)) < acc[workers]
    wrong = (truth[:, None]
             + rng.integers(1, n_classes, (n_tasks, n_votes))) % n_classes
    labels = np.where(right, truth[:, None], wrong).astype(np.int32)
    mask = rng.random((n_tasks, n_votes)) < 0.9
    return labels, workers.astype(np.int32), mask


def phase_kernels(*, n_tasks: int, n_votes: int, n_workers: int,
                  n_classes: int, n_reps: int, n_rows: int, n_logits: int,
                  iters: int = 20, seed: int = 0) -> dict:
    """The two Pallas kernels as the program selects them. Returns each
    check's error against its reference and whether its compiled program
    holds a Mosaic kernel (``tpu_custom_call``), which the TPU backend
    requires."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ref
    from repro.labelstream import aggregate
    from repro.learning import linear

    rng = np.random.default_rng(seed)
    W, C = n_workers, n_classes
    labels, workers, mask = _synthetic_votes(rng, n_tasks, n_votes, W, C)
    use_kernel, interpret = aggregate.estep_mode()
    args = (jnp.asarray(labels), jnp.asarray(workers), jnp.asarray(mask),
            W, C, iters, False)
    text = aggregate._ds_jit.lower(*args, use_kernel, interpret) \
        .compile().as_text()
    got = aggregate.dawid_skene(labels, workers, mask, n_workers=W,
                                n_classes=C, iters=iters)
    want = aggregate.dawid_skene(labels, workers, mask, n_workers=W,
                                 n_classes=C, iters=iters, use_kernel=False)
    ds = dict(shape=[n_tasks, n_votes, W, C],
              mosaic="tpu_custom_call" in text,
              max_abs_err=float(np.abs(np.asarray(got["posterior"])
                                       - np.asarray(want["posterior"])).max()))

    # vmapped: one EM per replication, the kernel under a batch axis
    lab_b, wk_b, m_b = (np.stack(x) for x in zip(*[
        _synthetic_votes(rng, n_tasks, n_votes, W, C)
        for _ in range(n_reps)]))
    bargs = (jnp.asarray(lab_b), jnp.asarray(wk_b), jnp.asarray(m_b),
             W, C, iters, False)
    btext = aggregate._ds_batch_jit.lower(*bargs, use_kernel, interpret) \
        .compile().as_text()
    bgot = aggregate.dawid_skene_batch(lab_b, wk_b, m_b, n_workers=W,
                                       n_classes=C, iters=iters)
    bwant = aggregate.dawid_skene_batch(lab_b, wk_b, m_b, n_workers=W,
                                        n_classes=C, iters=iters,
                                        use_kernel=False)
    ds_batch = dict(shape=[n_reps, n_tasks, n_votes, W, C],
                    mosaic="tpu_custom_call" in btext,
                    max_abs_err=float(np.abs(
                        np.asarray(bgot["posterior"])
                        - np.asarray(bwant["posterior"])).max()))

    lg = jax.random.normal(jax.random.key(seed), (n_rows, n_logits)) * 3
    fn = jax.jit(linear.entropy_from_logits)
    etext = fn.lower(lg).compile().as_text()
    h, h_ref = np.asarray(fn(lg)), np.asarray(ref.entropy_ref(lg))
    ent = dict(shape=[n_rows, n_logits], mosaic="tpu_custom_call" in etext,
               max_abs_err=float(np.abs(h - h_ref).max()),
               within_tol=bool(np.allclose(h, h_ref, atol=1e-3, rtol=1e-2)))
    checks = (ds, ds_batch, ent)
    mosaic = all(c["mosaic"] for c in checks) \
        or jax.default_backend() != "tpu"
    return dict(dawid_skene=ds, dawid_skene_batch=ds_batch, entropy=ent,
                ok=bool(mosaic and ds["max_abs_err"] <= 1e-4
                        and ds_batch["max_abs_err"] <= 1e-4
                        and ent["within_tol"]))


def _serve_run(cfg, n_ticks: int, seed: int):
    """``n_ticks`` serve ticks under a fixed injection schedule. Returns
    the digest of every output, the tasks injected, the tasks accounted
    for (finalized, queued, in flight or dropped) and those finalized."""
    import numpy as np

    from repro.labelstream.router import serve_init, serve_tick

    S = cfg.n_shards
    st = serve_init(cfg, seed=seed)
    base = np.zeros((S,), np.int64)
    chunks, fin, dropped = [], 0, 0
    for i in range(n_ticks):
        n = np.asarray([(i + s) % 3 for s in range(S)], np.int32)
        st, o = serve_tick(cfg, st, n, base.astype(np.int32))
        base += n
        o = {k: np.asarray(v) for k, v in o.items()}
        chunks.extend(o[k] for k in sorted(o))
        fin += int(o["fin"].sum())
        dropped += int(o["dropped"].sum())
    accounted = (fin + dropped + int(o["backlog"].sum())
                 + int(o["in_flight"].sum()))
    return _digest(chunks), int(base.sum()), accounted, fin


def phase_four_chips(n_devices: int, *, horizon: int, n_reps: int,
                     n_ticks: int, seed: int = 3) -> dict:
    """``run_stream`` and ``serve_tick`` of the sharded scenario on
    ``n_devices`` devices against one device, in this process."""
    import jax
    import numpy as np

    from repro.labelstream.router import run_stream
    from repro.scenarios import get_scenario
    from repro.scenarios.compile import to_serve_config, to_stream_config

    spec1 = get_scenario(SHARDED_SCENARIO)
    specD = get_scenario(SHARDED_SCENARIO,
                         {"sharding.n_devices": n_devices})
    # 10x the offered rate so backlogs queue and cross-shard steals fire
    kw = dict(n_reps=n_reps, seed=seed, rate_scale=10.0)
    out1 = run_stream(to_stream_config(spec1), horizon, **kw)
    outD = run_stream(to_stream_config(specD), horizon, **kw)

    def leaves(out):
        return [leaf for k in sorted(out)
                for leaf in jax.tree_util.tree_leaves(out[k])]

    arrived = int(np.asarray(outD["arrived"]).sum())
    accounted = sum(int(np.asarray(outD[k]).sum()) for k in
                    ("done_all", "dropped", "backlog_end", "in_flight_end"))
    stream = dict(digest_1=_digest(leaves(out1)),
                  digest_n=_digest(leaves(outD)),
                  arrived=arrived, accounted=accounted,
                  stolen=int(np.asarray(outD["stolen"]).sum()))
    stream["ok"] = (stream["digest_1"] == stream["digest_n"]
                    and arrived == accounted)

    d1, inj1, acc1, fin1 = _serve_run(to_serve_config(spec1), n_ticks, seed)
    dD, injD, accD, finD = _serve_run(to_serve_config(specD), n_ticks, seed)
    serve = dict(digest_1=d1, digest_n=dD, injected=injD, accounted=accD,
                 finalized=finD)
    serve["ok"] = d1 == dD and injD == accD and inj1 == acc1
    return dict(n_devices=n_devices, horizon=horizon, n_reps=n_reps,
                n_ticks=n_ticks, run_stream=stream, serve_tick=serve,
                ok=bool(stream["ok"] and serve["ok"]))


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def _run_phase(tag, fn, *args, **kw) -> bool:
    try:
        rec = fn(*args, **kw)
    except Exception:                      # report, then fail the smoke
        traceback.print_exc()
        print(f"{tag}: FAILED with an exception", flush=True)
        return False
    _log(tag, rec)
    return bool(rec["ok"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded tick on four chips against "
                         "one")
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    import jax
    devs = jax.devices()
    device = dict(platform=devs[0].platform, kind=devs[0].device_kind,
                  count=len(devs))
    print(f"device: platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}", flush=True)
    if device["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform "
              f"{device['platform']!r}", file=sys.stderr)
        return 1

    if args.four_chips:
        if len(devs) < 4:
            print(f"chip_smoke --four-chips: needs 4 chips, found "
                  f"{len(devs)}", file=sys.stderr)
            return 1
        ok = _run_phase("four_chips", phase_four_chips, 4, horizon=300,
                        n_reps=2, n_ticks=32)
    else:
        results = [
            _run_phase("serve", phase_serve, SERVE_SCENARIO,
                       SERVE_OVERRIDES, n_clients=4, per_client=16),
            _run_phase("lm_serve", phase_serve, LM_SCENARIO, LM_OVERRIDES,
                       n_clients=2, per_client=4, texts=True),
            _run_phase("kernels", phase_kernels, n_tasks=4096, n_votes=5,
                       n_workers=64, n_classes=10, n_reps=4, n_rows=1024,
                       n_logits=128),
        ]
        ok = all(results)
    if not ok:
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
