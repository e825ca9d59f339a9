"""Mean host-clock time of the window's serve ticks that ran the offline
Dawid-Skene refresh (``serve.refresh_tick``: the tick call and the fetch
of its answers)."""
from spans import span_ms


def read(run):
    return span_ms(run, "serve.refresh_tick")
