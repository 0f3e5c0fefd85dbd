"""Median over every pod due in the window of bind stamp minus due time."""
from benchmark.layers import bind_latencies, nearest_rank


def read(run):
    return nearest_rank(bind_latencies(run), 0.50)
