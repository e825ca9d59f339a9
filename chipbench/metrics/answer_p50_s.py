"""Median due-to-answer latency of the requests due in the window."""
from readers import answer_pct_s


def read(run):
    return answer_pct_s(run, 50)
