"""Find a served cell's knee (open loop) or saturating concurrency
(closed loop) by one sweep on the chip, in one process.

    python chipbench/sweep.py --workload crowd1k.poisson --param rate \\
        --values 500,1000,2000,4000 --seconds 10 --seed 5

For each value the cell's traffic file is copied with ``--param`` set to
it and the cell runs once; one JSON line per value gives the answered
rate, the latency percentiles, the generator's lateness, how the
requests pending or in the system moved over the window (first third
against last third), the host-loop and tick times, and whether the run
was correct. The rule that picks the cell's fixed value from these lines
is in PERF.md.
"""
from __future__ import annotations

import time

T_PROC0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parent / "src"))

import check  # noqa: E402
import loader  # noqa: E402
import readers  # noqa: E402
import run as bench  # noqa: E402


def summary(r: dict, cfg: dict) -> dict:
    rec = r["records"]
    late = sorted(s - d for s, d in zip(rec["sent"], rec["due"])
                  if d < r["seconds"])
    occ = r["occupancy"]
    third = max(1, len(occ) // 3)
    first = sum(o for _, o in occ[:third]) / third
    last = sum(o for _, o in occ[-third:]) / third
    return dict(
        answered_per_s=readers.answered_per_s(r),
        p50_s=readers.answer_pct_s(r, 50), p95_s=readers.answer_pct_s(r, 95),
        late_p50_s=readers.percentile(late, 50),
        late_p99_s=readers.percentile(late, 99),
        occupancy_first=first, occupancy_last=last,
        tick_ms=readers.tick_ms(r), host_loop_ms=readers.host_loop_ms(r),
        embed_ms=readers.embed_ms(r), ticks=r["ticks"],
        compiles_in_window=r["compiles_in_window"], setup_s=r["setup_s"],
        correct=check.passed(r["checks"], cfg), checks=r["checks"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--param", required=True)
    ap.add_argument("--values", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    c = loader.cell(args.workload)
    bench.setup_jax()
    if bench.device_info(c["workload"]["chips"]) is None:
        return 2
    mod = loader.runner(c["config"])
    with tempfile.TemporaryDirectory() as d:
        for v in args.values.split(","):
            traffic = dict(c["traffic"], **{args.param: float(v)})
            f = pathlib.Path(d) / "traffic.json"
            f.write_text(json.dumps(traffic))
            r = mod.run_cell(c["config"], f, seed=args.seed,
                             seconds=args.seconds, trace=False,
                             t_proc0=time.monotonic())
            print(json.dumps(dict({args.param: float(v)}, **summary(r, c["config"]))),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
