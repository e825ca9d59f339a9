"""Mean self time of the window's ``serve.tick`` spans (less their
``serve.dispatch``): waiting for the tick's device work and fetching its
answers."""
from spans import self_ms


def read(run):
    return self_ms(run, "serve.tick")
