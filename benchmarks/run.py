"""Benchmark orchestrator. One section per paper table/figure.
Prints ``name,us_per_call,derived`` CSV (benchmarks/common.py).

``--smoke`` runs a CI-sized subset: every bench module must import, and the
vectorized engine + kernels execute one tiny config each.
"""
from __future__ import annotations

import sys
import time


def main() -> None:
    smoke = "--smoke" in sys.argv
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    # bench_simfast forces one XLA host device per core; import it before
    # anything initializes jax so the flag takes effect
    from benchmarks import bench_simfast
    from benchmarks import (bench_workers, bench_straggler, bench_pool,
                            bench_combined, bench_embed, bench_grid,
                            bench_hybrid, bench_e2e, bench_kernels,
                            bench_labelstream, bench_serve, roofline)
    print("name,us_per_call,derived")
    t0 = time.time()
    if smoke:
        print("# --- smoke: vectorized engine ---", flush=True)
        bench_simfast.run(smoke=True)
        print("# --- smoke: event-loop engine ---", flush=True)
        bench_straggler.run(n_tasks=20, seeds=(3,))
        print("# --- smoke: pallas kernels (interpret) ---", flush=True)
        bench_kernels.run(validate_only=True)
        print("# --- smoke: hybrid learning (vec vs scalar, "
              "repro.scenarios facade) ---", flush=True)
        bench_hybrid.run(smoke=True)
        print("# --- smoke: labelstream service (repro.scenarios registry; "
              "worker-aware routing + admission sections) ---", flush=True)
        bench_labelstream.run(smoke=True)
        print("# --- smoke: grid engine (one compile per static class "
              "vs per-cell runs) ---", flush=True)
        bench_grid.run(smoke=True)
        print("# --- smoke: live serving front end (wall-clock answer "
              "latency through the jitted serve tick) ---", flush=True)
        bench_serve.run(smoke=True)
        print("# --- smoke: LM-embedding features (encoder throughput + "
              "chance_hard recovery) ---", flush=True)
        bench_embed.run(smoke=True)
        print(f"# total {time.time()-t0:.1f}s", flush=True)
        return
    for mod, tag in ((bench_workers, "worker latency CDFs (Fig 2)"),
                     (bench_straggler, "straggler (Fig 9-11, s4.1)"),
                     (bench_pool, "pool maintenance (Fig 3-8)"),
                     (bench_combined, "combined + TermEst (Fig 12-14)"),
                     (bench_hybrid, "hybrid learning (Fig 15-16)"),
                     (bench_e2e, "end-to-end (Fig 17-18, s6.6)"),
                     (bench_simfast, "vectorized engine vs event loop"),
                     (bench_kernels, "pallas kernels"),
                     (bench_labelstream,
                      "labelstream streaming service + worker-aware routing"),
                     (bench_grid,
                      "grid engine: Scenario×Policy table, one compile "
                      "per static class"),
                     (bench_serve,
                      "live serving front end (wall-clock SLOs)"),
                     (bench_embed,
                      "LM-embedding task features (encoder + chance_hard "
                      "recovery)"),
                     (roofline, "roofline (dry-run artifacts)")):
        print(f"# --- {tag} ---", flush=True)
        mod.run()
    print(f"# total {time.time()-t0:.1f}s", flush=True)


if __name__ == '__main__':
    main()
