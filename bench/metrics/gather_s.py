"""Seconds per campaign in the program's ``gather`` spans: the host
gathers of feature rows that feed the device (candidates, the commit's
rows, the test set, the labeled set before each retrain)."""
from bench.layers import mean_span


def read(data):
    value = mean_span(data, ("gather",))
    return value if value else None
