"""Run one benchmark cell once on the chip and print its result line.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell, its configuration, its traffic and its metrics come from
``BENCHMARK.json`` and the files it names (``loader.py``). The run starts
the clock, imports JAX with the persistent compilation cache in the
checkout, checks the device, sets up and warms up, measures for
``--seconds``, and checks what the timed path produced against the plain
reference (``check.py``). Earlier lines give the generator's lateness,
the compilations inside the window (there should be none) and the numbers
compared, each beside its limit; the last line of standard output is one
JSON object with ``correct``, ``attempted``, ``failed``, ``metrics`` and
``device`` (and with ``--trace 1`` a ``breakdown``). ``--trace 0`` reports
the cell's end-to-end metrics, ``--trace 1`` its per-layer metrics.

Off a TPU, or on fewer chips than the cell asks for, it names what it
found and exits non-zero without a result.
"""
from __future__ import annotations

import time

T_PROC0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parent / "src"))

import check  # noqa: E402
import loader  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_info(chips: int, *, require_tpu: bool = True):
    """The devices JAX sees; None (after saying why on stderr) when they
    are not the TPU chips the cell needs."""
    import jax
    devs = jax.devices()
    plat = devs[0].platform
    if require_tpu and plat != "tpu":
        print(f"chipbench: needs a TPU; JAX found platform {plat!r} "
              f"({devs[0].device_kind})", file=sys.stderr)
        return None
    if len(devs) < chips:
        print(f"chipbench: the cell needs {chips} chips; JAX found "
              f"{len(devs)} {plat} device(s)", file=sys.stderr)
        return None
    return dict(platform=plat, kind=devs[0].device_kind, count=len(devs))


def setup_jax():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    import jax
    # every program of the run goes to the cache, however fast it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def result(c: dict, run: dict, device: dict, trace: bool) -> dict:
    """The result line's object for a finished run."""
    rec = run["records"]
    due = [i for i, d in enumerate(rec["due"]) if d < run["seconds"]]
    done = sum(1 for i in due if rec["http"][i] == 200
               and rec["status"][i] == "done")
    metrics = {}
    for m in (c["per_layer"] if trace else c["end_to_end"]):
        v = loader.reader(m["name"])(run)
        if v is not None and math.isfinite(v):
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=int(run["memory_peak_bytes"]))
    out = dict(correct=check.passed(run["checks"], c["config"]),
               attempted=len(due),
               failed=len(due) - done, metrics=metrics, device=dev)
    tr = run.get("trace")
    if trace:
        dev["busy_s"] = tr["busy_s"] if tr else 0.0
        dev["window_s"] = tr["window_s"] if tr else run.get(
            "trace_window_s", 0.0)
        if tr:
            out["breakdown"] = dict(
                device_ops=[[n, s] for n, s in tr["device_ops"]],
                idle_gaps=tr["idle_gaps"])
    out["checks"] = check.lines(run["checks"], c["config"])
    return out


def report(run: dict, out: dict, stream=sys.stdout):
    """The earlier lines: generator lateness, the longest stall of each
    process's event loop, compilations in the window, the latency, and the
    numbers compared."""
    import readers
    rec = run["records"]
    late = sorted(s - d for s, d in zip(rec["sent"], rec["due"])
                  if d < run["seconds"])
    print("generator lateness_s: " + json.dumps(dict(
        n=len(late), p50=readers.percentile(late, 50),
        p99=readers.percentile(late, 99), max=late[-1] if late else None)),
        file=stream)
    print("longest late wake-up [at_s, late_s]: " + json.dumps(dict(
        generator=rec.get("loop_lag"), server=run["server_loop_lag"])),
        file=stream)
    print(f"compiles in window: {run['compiles_in_window']}", file=stream)
    lat = [x for x in readers.latencies(run) if math.isfinite(x)]
    print("latency_s: " + json.dumps(dict(
        p50=readers.percentile(lat, 50), p95=readers.percentile(lat, 95),
        ticks=run["ticks"], window_s=run["window_s"],
        checked_ticks=run["checks"].get("checked_ticks"),
        known_answers=run["checks"].get("known_answers"),
        mismatch_fields=run["checks"].get("mismatch_fields"))), file=stream)
    for k, v in out["checks"].items():
        print(f"check {k}: {v['value']} (limit {v['rule']} {v['limit']})",
              file=sys.stderr)


def main(argv=None) -> int:
    args = parse(argv)
    c = loader.cell(args.workload)
    setup_jax()
    device = device_info(c["workload"]["chips"])
    if device is None:
        return 2
    loader.peaks(device["kind"])
    run = loader.runner(c["config"]).run_cell(
        c["config"], c["traffic_file"], seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), t_proc0=T_PROC0)
    out = result(c, run, device, bool(args.trace))
    report(run, out)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
