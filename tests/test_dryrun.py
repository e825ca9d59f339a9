"""Integration test of the multi-pod dry-run path itself: run
repro.launch.dryrun in a subprocess (it must own jax initialization to set
the 512-host-device flag) for one cheap cell per step-kind and validate the
artifact schema the roofline harness consumes."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout=900):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun"] + args,
        cwd=REPO, env=env, capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("arch,shape", [("xlstm-125m", "decode_32k")])
def test_dryrun_cell_compiles_and_reports(arch, shape, tmp_path):
    out = str(tmp_path)
    r = _run(["--arch", arch, "--shape", shape, "--mesh", "single",
              "--out", out, "--tag", "t"])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "OK" in r.stdout
    rec = json.load(open(os.path.join(tmp_path, f"{arch}_{shape}_single_t.json")))
    assert rec["status"] == "ok"
    assert rec["n_chips"] == 256
    for k in ("compute_s", "memory_s", "collective_s"):
        assert rec["roofline"][k] >= 0
    assert rec["memory"]["peak_per_device_gb"] < 16.0   # fits v5e HBM
    assert rec["per_device"]["flops"] >= 0
    assert "collective_by_kind" in rec["per_device"]


def test_dryrun_skips_unsupported_cell(tmp_path):
    r = _run(["--arch", "qwen2.5-14b", "--shape", "long_500k",
              "--mesh", "single", "--out", str(tmp_path)], timeout=300)
    # unsupported cells are declared skips, not failures
    assert r.returncode == 0
    assert "SKIP" in r.stdout
