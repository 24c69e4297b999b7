"""Median request latency over every request of the window, by nearest
rank; a request not answered counts as infinitely late."""
from perfbench.readings import nearest_rank


def read(run):
    return nearest_rank(run["latency_s"], 50) * 1e3
