"""Mean host-clock time of the window's text embedding calls."""
from readers import embed_ms


def read(run):
    return embed_ms(run)
