"""Mean wait of the window's answered requests from the server's parse to
their injection into a tick."""
from spans import queue_wait_ms


def read(run):
    return queue_wait_ms(run)
