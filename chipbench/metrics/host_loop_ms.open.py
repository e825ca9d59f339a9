"""Mean host time per tick outside the device calls: HTTP front end and serve loop."""
from readers import host_loop_ms


def read(run):
    return host_loop_ms(run)
