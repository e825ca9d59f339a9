"""chip_smoke.py rehearsed on the CPU at a tiny size.

The phases are functions of their sizes, so the same code the chip runs
is checked here: the served path and the LM served path through
``LabelServer`` over loopback HTTP, and the kernel checks (which select
the jnp references off-TPU). Run as a script off-TPU, the smoke must
refuse and print no result.
"""
import importlib.util
import json
import os
import pathlib
import subprocess
import sys

import pytest

_SMOKE = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(_SMOKE)], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


@pytest.mark.parametrize("scenario,overrides,texts", [
    ("serve_default", {"pool.n_shards": 2, "pool.pool_size": 8,
                       "window": 16, "backlog": 64}, False),
    ("lm_stream", {}, True),
])
def test_serve_phase_answers_everything(smoke, scenario, overrides, texts):
    rec = smoke.phase_serve(scenario, overrides, n_clients=3, per_client=3,
                            texts=texts)
    assert rec["ok"], rec
    assert rec["answered"] == rec["submitted"] == 9
    assert rec["conservation"] is True
    assert rec["serve_tick"]["calls"] == rec["ticks"]
    if texts:
        assert rec["serve_embed"]["calls"] >= 1
    json.dumps(rec)                  # the smoke prints it as one line


def test_kernel_phase_matches_references(smoke):
    rec = smoke.phase_kernels(n_tasks=64, n_votes=5, n_workers=8,
                              n_classes=3, n_reps=2, n_rows=64, n_logits=128,
                              iters=5)
    assert rec["ok"], rec
    # off-TPU the program selects the jnp references, not Mosaic
    assert not any(rec[k]["mosaic"] for k in
                   ("dawid_skene", "dawid_skene_batch", "entropy"))
