"""Each cell's functions run at a tiny size on the CPU and give a result
line with the contract's keys and finite metrics; the command refuses to
run off a TPU. (Times from these runs say nothing about the chip.)"""
import copy
import json
import math
import os
import pathlib
import shutil
import subprocess
import sys
import time

import pytest

import loader
import run as bench

ROOT = pathlib.Path(__file__).resolve().parents[2]
TINY = {"pool.n_shards": 2, "pool.pool_size": 16, "window": 32,
        "backlog": 256}
TINY_REF = {"n_shards": 2, "pool_size": 16, "window": 32, "backlog": 256}
# the encoder at the program's reduced (smoke) widths, for the CPU only
TINY_EMBED = {"embed.reduced": True, "embed.seq_len": 16,
              "embed.bank_size": 64, "embed.batch_size": 16}
TINY_ENCODER = {"d_model": 64, "n_layers": 2, "n_heads": 4, "head_dim": 16,
                "vocab_size": 256, "ff_inner": 128, "seq_len": 16,
                "batch_size": 16, "sample_rows": 16}
LIGHT = {"rate": 300.0, "clients": 16}
CELLS = [w["name"] for w in loader.benchmark()["workloads"]]


def tiny_config(cfg: dict) -> dict:
    """The configuration at a size the CPU runs in seconds."""
    cfg = copy.deepcopy(cfg)
    cfg["overrides"].update(TINY)
    cfg["reference"].update(TINY_REF)
    for k in ("learner_matmul_inputs", "learner_fit_matmul_inputs"):
        if k in cfg["reference"]:
            # the CPU backend multiplies float32 exactly
            cfg["reference"][k] = "float32"
    if "encoder" in cfg:
        cfg["overrides"].update(TINY_EMBED)
        cfg["encoder"].update(TINY_ENCODER)
        # at these widths the CPU reads 0.01-0.04 for the program and
        # 0.13-0.35 for the float8 control
        cfg["limits"]["embed_rel_err"] = 0.1
    return cfg


def tiny_run(cell, tmp_path, seed, trace=False, **kw):
    c = loader.cell(cell)
    c["config"] = tiny_config(c["config"])
    traffic = {k: LIGHT.get(k, v) for k, v in c["traffic"].items()}
    if "text" in traffic:
        traffic["rate"] = 100.0
    f = tmp_path / "traffic.json"
    f.write_text(json.dumps(traffic))
    r = loader.runner(c["config"]).run_cell(
        c["config"], f, seed=seed, seconds=2.0, trace=trace,
        t_proc0=time.monotonic(), **kw)
    return c, r


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_cell_result_line(cell, trace, tmp_path):
    c, r = tiny_run(cell, tmp_path, seed=2 ** 31 + 17, trace=trace)
    dev = dict(platform="cpu", kind="cpu", count=1)
    out = bench.result(c, r, dev, trace)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert r["compiles_in_window"] == 0
    want = c["per_layer"] if trace else c["end_to_end"]
    for m in want:
        if m["source"] == "device_trace":
            continue        # no chip in the CPU trace
        assert math.isfinite(out["metrics"][m["name"]]["value"]), m["name"]
    if trace:
        assert {"busy_s", "window_s"} <= set(out["device"])


def test_cli_refuses_off_tpu():
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert "'cpu'" in p.stderr
    assert p.stdout.strip() == ""


def test_cli_fails_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
