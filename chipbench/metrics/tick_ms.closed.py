"""Mean host-clock time of the window's serve ticks (tick and fetch of its answers)."""
from readers import tick_ms


def read(run):
    return tick_ms(run)
