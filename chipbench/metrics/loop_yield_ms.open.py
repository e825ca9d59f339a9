"""Mean ``serve.yield`` span of the window: the event loop's other work,
mostly HTTP, between the end of one tick and the start of the next."""
from spans import span_ms


def read(run):
    return span_ms(run, "serve.yield")
