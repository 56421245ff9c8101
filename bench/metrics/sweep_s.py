"""Seconds per campaign in the program's ``sweep`` spans."""
from bench.layers import mean_span


def read(data):
    value = mean_span(data, ("sweep",))
    return value if value else None
