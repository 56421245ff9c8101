"""Seconds per campaign in the program's ``fit`` spans."""
from bench.layers import mean_span


def read(data):
    value = mean_span(data, ("fit",))
    return value if value else None
