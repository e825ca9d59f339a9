"""The encoder's share of the chip's bf16 peak while it runs.

Operations of the real (unpadded) rows, counted from the encoder's
shapes (``flops.py``), over the device time of the encoder's program
(``_embed_batch``) in the trace times the peak: the window's mean real
rows per encoder call, times the calls in the trace, times the operations
per row, over their summed device time."""
import json
import pathlib

import flops

HERE = pathlib.Path(__file__).resolve().parents[1]


def read(run):
    tr, enc = run.get("trace"), run.get("encoder")
    if not tr or not enc or not run.get("embed_rows_per_batch"):
        return None
    names = [n for n in tr["module_time"] if "_embed_batch" in n]
    secs = sum(tr["module_time"][n] for n in names)
    calls = sum(tr["module_count"][n] for n in names)
    if not calls or secs <= 0:
        return None
    peaks = json.loads((HERE / "peaks.json").read_text())["devices"]
    peak = peaks[run["device_kind"]]["bf16_flops"]
    work = calls * run["embed_rows_per_batch"] \
        * flops.encoder_flops_per_row(enc)
    return 100.0 * work / (secs * peak)
