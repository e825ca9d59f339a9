"""One run of a served cell whose deployment runs the offline Dawid-Skene
refresh: ``served.py``'s session with the refresh's own tick check.

The server, the load generator and the window are ``served.py``'s. What
differs:

- the recorded ticks are judged by ``check_ds.check_ticks`` against
  ``reference/crowd_tick_ds.py``, the refresh included;
- the configuration must also state the reference's ``refresh_iters``;
- the load stays on 1.5 s after the window, not 0.5 s: the recorded ticks
  fetch the state and run about 10 ms apart, and the check needs a dozen
  refresh ticks among them (up to 96 ticks are recorded);
- a tick that raised (one the compiler refused) stops the run with its
  own error, not only the warm-up's;
- a traced run adds, from the same profile (``trace_reduce.py``), each
  serve-tick launch's device time and whether it ran the refresh
  (``trace["tick_launches"]``: ``[refresh, seconds]`` pairs; a launch ran
  it when an E-step kernel op lies inside it), and the E-step kernel's
  device time and call count with the shapes of one call and its real
  votes, the mean over the checked refreshes (``trace["estep"]``). The
  kernel's ops are those named after it (``pallas_call(...,
  name="ds_estep")`` in ``kernels/ds_estep.py``).

Readings behind the limits, on the chip:

    python chipbench/served_ds.py --workload cub200.poisson \\
        --seeds 11,12,13 --seconds 5

prints one JSON line per seed with the program's numbers and the
control's: the reference in bfloat16, EM included, in the program's
place (it must fail).
"""
from __future__ import annotations

import time

T_PROC0 = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
if __name__ == "__main__":
    sys.path.insert(0, str(HERE))
    sys.path.insert(1, str(HERE.parent / "src"))

import check  # noqa: E402
import check_ds  # noqa: E402
import served  # noqa: E402
import trace_reduce  # noqa: E402

TICK_MODULE = "_serve_tick_jit"
ESTEP_OP = "ds_estep"


class TickWatch:
    """Stands in for ``serve_tick`` in front of the recorder and keeps the
    first error a call raised (a tick the compiler refused)."""

    def __init__(self, real):
        self.real = real
        self.error = None

    def __call__(self, *args, **kwargs):
        try:
            return self.real(*args, **kwargs)
        except Exception as e:
            self.error = self.error or e
            raise


def check_refresh_semantics(spec, ref: dict):
    from repro.scenarios.compile import to_serve_config
    c = to_serve_config(spec)
    if c.refresh_iters != ref["refresh_iters"]:
        raise ValueError(f"the program's refresh_iters {c.refresh_iters} "
                         f"differs from the reference's "
                         f"{ref['refresh_iters']}")


def launches(tr: dict) -> tuple:
    """Each serve-tick launch's ``[refresh, device seconds]`` on the
    device planes, and the E-step ops' ``(calls, device seconds)``."""
    out, calls, secs = [], 0, 0.0
    for p in trace_reduce.device_planes(tr):
        mods = trace_reduce._line(p, trace_reduce.MODULES_LINE)
        ops = trace_reduce._line(p, trace_reduce.OPS_LINE)
        ev = [(s, d) for n, s, d in (ops["events"] if ops else [])
              if trace_reduce.op_name(n).startswith(ESTEP_OP)]
        calls += len(ev)
        secs += sum(d for _, d in ev) * 1e-9
        starts = sorted(s for s, _ in ev)
        for n, s, d in (mods["events"] if mods else []):
            if TICK_MODULE in n:
                hit = any(s <= x < s + d for x in starts)
                out.append([hit, d * 1e-9])
    return out, calls, secs


def reduce_trace(run: dict, ref: dict):
    path = trace_reduce.find_xplane(run["trace_dir"])
    if path is None:
        return None
    tr = trace_reduce.from_profile(path)
    shutil.rmtree(run["trace_dir"], ignore_errors=True)
    red = trace_reduce.reduce(tr, run["trace_window_s"])
    if red is None:
        return red
    ticks, calls, secs = launches(tr)
    red["tick_launches"] = ticks
    red["estep"] = dict(calls=calls, device_s=secs,
                        tasks=ref["n_shards"] * ref["window"],
                        slots=ref["votes_cap"], classes=ref["n_classes"])
    return red


def run_cell(cfg: dict, traffic_file, *, seed: int, seconds: float,
             trace: bool, t_proc0: float, tail_s=1.5, drain_s=60.0,
             trace_s=None, n_warm=16, fault=None, workdir=None) -> dict:
    """Serve the configuration under the traffic for one window and return
    the run's record (``served._session``'s), with the comparison's
    numbers under ``checks``."""
    import repro.labelstream.router as router

    server_seed = seed % (2 ** 31 - 1)
    spec = served.server_spec(cfg)
    ref = cfg["reference"]
    served.check_semantics(spec, ref)
    check_refresh_semantics(spec, ref)
    watch = TickWatch(router.serve_tick)
    recorder = served.TickRecorder(watch)
    recorder.fault = fault
    recorder.annotate = trace
    own_dir = workdir is None
    workdir = pathlib.Path(workdir or tempfile.mkdtemp(prefix="chipbench"))
    router.serve_tick = recorder
    try:
        if trace_s is None:
            trace_s = min(2.0, 0.4 * seconds) if trace else 0.0
        try:
            run = asyncio.run(served._session(
                spec, traffic_file, seed=seed, seconds=seconds,
                tail_s=tail_s, drain_s=drain_s, trace_s=trace_s,
                recorder=recorder, t_proc0=t_proc0, n_warm=n_warm,
                workdir=workdir, armed=(recorder,)))
        except RuntimeError as e:
            if watch.error is not None:
                raise watch.error from e
            raise
        if trace:
            run["trace"] = reduce_trace(run, ref)
    finally:
        router.serve_tick = watch.real
        if own_dir:
            shutil.rmtree(workdir, ignore_errors=True)
    numbers = check.check_answers(ref, run["records"], run["reqs"],
                                  recorder.outs, run["stats"])
    numbers.update(check_ds.check_ticks(ref, recorder.checked,
                                        cfg["decision_eps"]))
    run["checks"] = numbers
    estep = (run.get("trace") or {}).get("estep")
    if estep and numbers["checked_refreshes"]:
        estep["votes"] = numbers["refresh_votes"] / numbers["checked_refreshes"]
    run["checked"] = recorder.checked
    run["server_seed"] = server_seed
    return run


def readings(cfg: dict, traffic_file, seed: int, seconds: float,
             t_proc0: float) -> dict:
    """The program's numbers and the bfloat16 control's for one run."""
    r = run_cell(cfg, traffic_file, seed=seed, seconds=seconds, trace=False,
                 t_proc0=t_proc0)
    prog = {k: v for k, v in r["checks"].items() if k != "mismatch_fields"}
    ctl = check_ds.check_ticks(cfg["reference"], r["checked"],
                               cfg["decision_eps"], control="bfloat16")
    ctl = dict(prog, **{k: v for k, v in ctl.items()
                        if k != "mismatch_fields"})
    return dict(seed=seed, program=prog,
                program_correct=check.passed(prog, cfg), control=ctl,
                control_correct=check.passed(ctl, cfg))


def main(argv=None) -> int:
    import loader
    import run as bench
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    c = loader.cell(args.workload)
    bench.setup_jax()
    if bench.device_info(c["workload"]["chips"]) is None:
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(c["config"], c["traffic_file"], seed,
                                  args.seconds, T_PROC0)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
