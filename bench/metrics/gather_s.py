"""Seconds per campaign in the program's ``gather`` spans: the host
gathers of the labeled set before each retrain and of the test set.  The
sweeps' candidate and commit rows are gathered page by page inside the
``sweep`` spans and count in ``sweep_s``."""
from bench.layers import mean_span


def read(data):
    value = mean_span(data, ("gather",))
    return value if value else None
