"""The Dawid-Skene E-step kernel's share of its roofline: per call, the
least time the chip needs for the work any implementation must do
(``kernel_work.py``: the indices, the rows the real votes name, the two
outputs; memory-bound at these shapes) over the kernel's mean traced
device time per call, at the peaks of ``peaks.json``. The real votes per
call are the mean over the run's checked refreshes."""
import json
import pathlib

import kernel_work

HERE = pathlib.Path(__file__).resolve().parents[1]


def read(run):
    es = (run.get("trace") or {}).get("estep")
    if not es or not es["calls"] or es["device_s"] <= 0 \
            or es.get("votes") is None:
        return None
    peak = json.loads((HERE / "peaks.json").read_text())["devices"][
        run["device_kind"]]
    T, V, C, N = es["tasks"], es["slots"], es["classes"], es["votes"]
    need = kernel_work.roofline_s(kernel_work.ds_estep_bytes(T, V, C, N),
                                  kernel_work.ds_estep_flops(T, C, N), peak)
    return 100.0 * need / (es["device_s"] / es["calls"])
