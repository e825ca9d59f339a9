"""Reduce a profiler trace to the benchmark's device numbers.

A trace is first turned into plain data (``from_profile``):
``{"planes": [{"name": ..., "lines": [{"name": ..., "events":
[[name, start_ns, duration_ns], ...]}]}]}``. The reduction works on that
form only, so the tests check it on a small hand-made trace
(``testdata/trace_small.json``).

On each device plane (``/device:TPU:<n>``) the ops are the events of the
``XLA Ops`` line and the programs those of ``XLA Modules``:

- busy time is the union of the op intervals, and the idle share is one
  minus busy over the traced window;
- op time by name sums each op's durations;
- collective time is the union of the collective ops' intervals
  (all-gather, all-reduce, reduce-scatter, all-to-all, collective-permute),
  and its exposed part is what of it no other op overlaps;
- idle gaps are the stretches between busy intervals, each named by the
  host span (``TraceAnnotation``) that covers most of it.
"""
from __future__ import annotations

import pathlib

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "/host:"
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def find_xplane(trace_dir):
    found = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    return found[-1] if found else None


def from_profile(path) -> dict:
    """The plain form of an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    planes = []
    for pl in pd.planes:
        lines = []
        for ln in pl.lines:
            lines.append(dict(name=ln.name, events=[
                [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                for ev in ln.events]))
        planes.append(dict(name=pl.name, lines=lines))
    return dict(planes=planes)


def union(intervals):
    """Merged, sorted ``[start, end]`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """Parts of merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def op_name(name: str) -> str:
    """An op event's instruction name (``%fusion.12 = f32[...] ...`` ->
    ``fusion.12``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def is_collective(name: str) -> bool:
    n = name.lower()
    return any(c in n for c in COLLECTIVES)


def _line(plane, name):
    return next((ln for ln in plane["lines"] if ln["name"] == name), None)


def device_planes(tr: dict):
    return [p for p in tr["planes"] if p["name"].startswith(DEVICE_PREFIX)]


def reduce(tr: dict, window_s: float, top: int = 10) -> dict:
    """Device numbers of a plain trace over a window of ``window_s``."""
    devs = device_planes(tr)
    per_dev, op_time, mod_time, mod_count = [], {}, {}, {}
    gaps_all = []
    host = [ev for p in tr["planes"] if p["name"].startswith(HOST_PREFIX)
            for ln in p["lines"] for ev in ln["events"]]
    for p in devs:
        ops = _line(p, OPS_LINE)
        evs = ops["events"] if ops else []
        busy = union([[s, s + d] for _, s, d in evs])
        coll = union([[s, s + d] for n, s, d in evs if is_collective(n)])
        comp = union([[s, s + d] for n, s, d in evs if not is_collective(n)])
        for n, _, d in evs:
            n = op_name(n)
            op_time[n] = op_time.get(n, 0.0) + d * 1e-9
        mods = _line(p, MODULES_LINE)
        for n, _, d in (mods["events"] if mods else []):
            mod_time[n] = mod_time.get(n, 0.0) + d * 1e-9
            mod_count[n] = mod_count.get(n, 0) + 1
        per_dev.append(dict(
            busy_s=total(busy) * 1e-9,
            collective_s=total(coll) * 1e-9,
            collective_exposed_s=total(subtract(coll, comp)) * 1e-9))
        gaps_all.extend([busy[i][1], busy[i + 1][0]]
                        for i in range(len(busy) - 1))
    if not per_dev:
        return None
    n = len(per_dev)
    busy_s = sum(d["busy_s"] for d in per_dev) / n
    busiest = max(per_dev, key=lambda d: d["busy_s"])
    if busy_s > window_s:
        # the ops' union cannot outlast the window they were traced in
        raise ValueError(f"device busy {busy_s} s over a traced window of "
                         f"{window_s} s: the trace reduction counts wrong")
    gaps = sorted(gaps_all, key=lambda g: g[0] - g[1])[:top]
    return dict(
        n_devices=n, window_s=window_s, busy_s=busy_s,
        idle_share=1.0 - busy_s / window_s if window_s > 0 else None,
        busiest=busiest,
        device_ops=sorted(op_time.items(), key=lambda kv: -kv[1])[:top],
        module_time=mod_time, module_count=mod_count,
        idle_gaps=[[gap_name(g, host), (g[1] - g[0]) * 1e-9] for g in gaps])


def gap_name(gap, host_events) -> str:
    """The host span covering most of an idle gap, or ``no span``."""
    best, cover = "no span", 0.0
    for name, s, d in host_events:
        c = min(gap[1], s + d) - max(gap[0], s)
        if c > cover:
            best, cover = name, c
    return best


def reduce_file(path, window_s: float) -> dict:
    return reduce(from_profile(path), window_s)
