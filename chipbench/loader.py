"""Find a cell's files by the names in ``BENCHMARK.json``.

- configuration ``<c>``: the file its entry names (``configs/<c>.json``),
  whose ``runner`` names the module that runs its cells;
- traffic ``<t>``: ``traffic/<t>.json``, whose ``kind`` names the generator
  ``traffic/kinds/<kind>.py``;
- metric ``<m>``: the reader ``metrics/<m>.py``, a ``read(run)`` function.

A cell reports the end-to-end metrics whose ``workloads`` list it, or that
have none, and the per-layer metrics chosen the same way.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


def benchmark(root=ROOT) -> dict:
    return json.loads((pathlib.Path(root) / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell: str, e2e_names) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") in e2e_names if "moves" in metric else True


def cell(name: str, root=ROOT) -> dict:
    b = benchmark(root)
    wl = next((w for w in b["workloads"] if w["name"] == name), None)
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; cells: "
                       + ", ".join(w["name"] for w in b["workloads"]))
    cfg_entry = next(c for c in b["configs"] if c["name"] == wl["config"])
    cfg = json.loads((pathlib.Path(root) / cfg_entry["file"]).read_text())
    traffic_file = HERE / "traffic" / f"{wl['traffic']}.json"
    traffic = json.loads(traffic_file.read_text())
    kind_file = HERE / "traffic" / "kinds" / f"{traffic['kind']}.py"
    if not kind_file.is_file():
        raise FileNotFoundError(f"no traffic kind at {kind_file}")
    e2e = [m for m in b["end_to_end"] if _applies(m, name, ())]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in b["per_layer"] if _applies(m, name, names)]
    return dict(workload=wl, config=cfg, config_entry=cfg_entry,
                traffic_file=traffic_file, traffic=traffic,
                end_to_end=e2e, per_layer=per_layer,
                run_seconds=b["run_seconds"])


def module(path: pathlib.Path, tag: str):
    spec = importlib.util.spec_from_file_location(
        tag + "_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str):
    path = HERE / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric {metric!r} at {path}")
    return module(path, "metric").read


def runner(cfg: dict):
    return module(HERE / f"{cfg['runner']}.py", "runner")


def peaks(device_kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"({', '.join(table['devices'])})")
    return table["devices"][device_kind]
