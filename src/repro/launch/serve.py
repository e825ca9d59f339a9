"""Launcher for the live labeling service: serve any registry stream
scenario over HTTP (``repro.serving.server.LabelServer``).

    PYTHONPATH=src python -m repro.launch.serve --scenario serve_default
    PYTHONPATH=src python -m repro.launch.serve --scenario serve_default \\
        --port 8787 --tick-interval-s 0.02

``--smoke`` runs the CI leg: start the server on an ephemeral port,
submit a small workload from concurrent clients, assert every submission
is answered with conservation intact, then shut down cleanly.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import sys


async def _serve_forever(args):
    from repro.scenarios import get_scenario
    from repro.serving.server import LabelServer

    spec = get_scenario(args.scenario)
    srv = LabelServer(spec, seed=args.seed, host=args.host, port=args.port,
                      tick_interval_s=args.tick_interval_s)
    await srv.start()
    print(f"serving scenario {args.scenario!r} on "
          f"http://{srv.host}:{srv.port}  (POST /tasks, GET /labels/<id>, "
          "GET /stats, POST /shutdown)", flush=True)
    try:
        await srv.wait_closed()
    finally:
        await srv.close()


async def drive(spec, *, seed: int = 0, host: str | None = None,
                n_clients: int = 4, per_client: int = 8,
                submission=None, timeout_s: float = 60.0) -> dict:
    """Serve ``spec`` on an ephemeral port and drive it over loopback HTTP.

    ``n_clients`` concurrent keep-alive clients each submit ``per_client``
    waited tasks; ``submission(i, k)``, if given, returns the extra
    ``ServeClient.submit`` fields (``text``/``label``) of client ``i``'s
    ``k``-th task. The server is shut down over HTTP afterwards. Returns
    the answered count, the server's ``/stats`` taken before shutdown,
    and ``ok``: every task answered with conservation intact."""
    from repro.serving.server import LabelServer, ServeClient

    srv = LabelServer(spec, seed=seed, host=host, port=0,
                      tick_interval_s=0.0)
    await srv.start()

    async def client(i):
        c = await ServeClient(srv.host, srv.port).connect()
        out = []
        for k in range(per_client):
            extra = submission(i, k) if submission else {}
            out.append(await c.submit(wait=True, timeout_s=timeout_s,
                                      **extra))
        await c.aclose()
        return out

    try:
        results = await asyncio.gather(*[client(i)
                                         for i in range(n_clients)])
        if srv.error is None:
            c = await ServeClient(srv.host, srv.port).connect()
            stats = await c.stats()
            await c.shutdown()
            await c.aclose()
        await srv.wait_closed()       # raises if the tick loop died
    finally:
        await srv.close()
    n = n_clients * per_client
    answered = sum(1 for out in results for status, r in out
                   if status == 200 and r["status"] == "done")
    return dict(submitted=n, answered=answered, stats=stats,
                ok=(answered == n and stats["conservation"]
                    and stats["answered"] == n))


async def _smoke(args):
    from repro.scenarios import get_scenario

    res = await drive(get_scenario(args.scenario), seed=args.seed,
                      host=args.host)
    stats = res["stats"]
    print(json.dumps(dict(
        submitted=res["submitted"], answered=res["answered"],
        conservation=stats["conservation"],
        p50_latency_s=stats["p50_latency_s"],
        p95_latency_s=stats["p95_latency_s"],
        ticks=stats["ticks"], ok=res["ok"])))
    if not res["ok"]:
        raise SystemExit("serve smoke FAILED: "
                         f"{res['answered']}/{res['submitted']} answered, "
                         f"stats={stats}")
    print("serve smoke OK", flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scenario", default="serve_default")
    ap.add_argument("--host", default=None)
    ap.add_argument("--port", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tick-interval-s", type=float, default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="run the CI smoke workload and exit")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    try:
        asyncio.run(_smoke(args) if args.smoke else _serve_forever(args))
    except KeyboardInterrupt:
        print("interrupted", file=sys.stderr)


if __name__ == "__main__":
    main()
