"""Campaign-loop self time per campaign: the bootstrap, iteration and
commit spans less the fit, sweep and k-center spans inside them -- the
host's power-law fits, joint search, test-set scoring and bookkeeping."""
from bench.layers import LAYER_SPANS, LOOP_SPANS, mean_span


def read(data):
    loop, inner = mean_span(data, LOOP_SPANS), mean_span(data, LAYER_SPANS)
    if loop is None:
        return None
    return loop - inner
