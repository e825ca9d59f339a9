"""Mean ``serve.dispatch`` span of the window: argument conversion and the
tick's call, up to its return on dispatch."""
from spans import span_ms


def read(run):
    return span_ms(run, "serve.dispatch")
