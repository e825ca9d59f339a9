"""Mean ticks from injection to answer of the window's answered requests,
counting both ends."""
from spans import answer_ticks


def read(run):
    return answer_ticks(run)
