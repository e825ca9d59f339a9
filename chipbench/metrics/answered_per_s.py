"""Labels answered ``done`` in the window, per second of the window."""
from readers import answered_per_s


def read(run):
    return answered_per_s(run)
