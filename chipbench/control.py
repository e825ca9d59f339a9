"""Readings behind the limits of ``check.py``, on the chip at a cell's size.

    python chipbench/control.py --workload <cell> --seeds 11,12,13 \\
        --seconds 5

For each seed, in this one process, the cell runs as the benchmark runs
it (with a window of ``--seconds``) and prints one JSON line with two
readings of every number compared:

- ``program``: the program's own run, judged against the float32
  reference (a sound run: the lower readings);
- ``control``: the reference computed a precision below the
  configuration's (the posterior and the learner in bfloat16; the
  encoder's matrix products in float8), put in the program's place on the same recorded
  ticks and texts and judged the same way (the upper readings; it must
  fail). The numbers the control does not recompute keep the program's.

The benchmark's own runs do not run the control.
"""
from __future__ import annotations

import time

T_PROC0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parent / "src"))

import check  # noqa: E402
import loader  # noqa: E402
import run as bench  # noqa: E402


def readings(cfg: dict, traffic_file, seed: int, seconds: float,
             t_proc0: float) -> dict:
    ref = cfg["reference"]
    r = loader.runner(cfg).run_cell(cfg, traffic_file, seed=seed,
                                    seconds=seconds, trace=False,
                                    t_proc0=t_proc0)
    prog = {k: v for k, v in r["checks"].items() if k != "mismatch_fields"}
    ctl = check.check_ticks(ref, r["checked"], cfg["decision_eps"],
                              control="bfloat16")
    if "encoder" in cfg:
        ctl.update(check.check_embeddings(
            cfg["encoder"], r["embed_sample"], r["server_seed"],
            dtype=cfg["encoder"]["control_dtype"]))
    ctl = dict(prog, **{k: v for k, v in ctl.items()
                        if k != "mismatch_fields"})
    out = dict(seed=seed, program=prog,
               program_correct=check.passed(prog, cfg), control=ctl,
               control_correct=check.passed(ctl, cfg))
    mi = ref.get("learner_fit_matmul_inputs")
    if mi is not None:
        # the learner's fit judged with its products' inputs read the
        # other way: which reading the chip's products follow
        other = "float32" if mi == "bfloat16" else "bfloat16"
        alt = check.check_ticks(dict(ref, learner_fit_matmul_inputs=other),
                                r["checked"], cfg["decision_eps"])
        out["program_fit_inputs_" + other] = {
            k: alt[k] for k in ("learn_err", "tick_mismatch")}
    return out


def main(argv=None) -> int:
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
                                  args.seconds, T_PROC0)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
