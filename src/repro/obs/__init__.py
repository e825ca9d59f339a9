"""repro.obs: in-loop trace buffers, latency-source decomposition and a
run-report layer over all three engines.

The pieces:

  * ``trace``  — :class:`TraceConfig`, the engine-native trace switch the
    jitted paths read (router/simfast), and :class:`EventsTrace`, the
    host-side recorder the scalar event loop fills;
  * ``timing`` — process-wide wall-clock registry: the program's spans
    (``timing.span``, also written to a profiler trace) and cold =
    compile+execute vs warm = execute per jitted entry point;
  * ``export`` — versioned JSON-lines trace artifacts written next to the
    ``BENCH_*.json`` files (``python -m repro.obs.export <scenario>``);
  * ``report`` — text dashboard over any trace artifact
    (``python -m repro.obs.report artifacts/TRACE_<scenario>.jsonl``).

This ``__init__`` deliberately exports only the engine-facing pieces
(``trace``/``timing`` — both import-light): ``export``/``report`` import
the engine modules lazily inside functions, so ``repro.labelstream`` /
``repro.core.simfast`` can import ``repro.obs.trace`` without a cycle.
"""
from repro.obs import timing
from repro.obs.trace import EventsTrace, TraceConfig

__all__ = ["EventsTrace", "TraceConfig", "timing"]
