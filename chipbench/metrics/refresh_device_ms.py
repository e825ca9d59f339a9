"""The refresh's cost on the chip's clock: the mean device time of a
traced serve-tick launch that ran the offline Dawid-Skene refresh, less
that of one that did not (``served_ds.py``'s ``tick_launches``)."""


def read(run):
    tr = run.get("trace") or {}
    ticks = tr.get("tick_launches") or []
    on = [s for hit, s in ticks if hit]
    off = [s for hit, s in ticks if not hit]
    if not on or not off:
        return None
    return 1e3 * (sum(on) / len(on) - sum(off) / len(off))
