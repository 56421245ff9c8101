"""Seconds per campaign in the program's ``search`` spans: the power-law
and cost-model fits and the joint search over them."""
from bench.layers import mean_span


def read(data):
    value = mean_span(data, ("search",))
    return value if value else None
