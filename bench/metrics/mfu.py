"""The labeler's model FLOPs over the window's campaigns, against the
chips' bf16 peak: retrains at 6 x MACs per labeled row per epoch and
scoring passes at 2 x MACs per row, unpadded rows only
(``bench.layers.campaign_flops``), over the campaigns' own seconds (a
traced window also holds the profiler's stop after its first campaign).
Float32 matmuls at default precision run as bf16 passes on the MXU, so
the bf16 peak is the one they can reach."""
from bench.layers import campaign_flops


def read(data):
    runs = [r for r in data["runs"] if r.committed]
    if not runs:
        return None
    total = sum(campaign_flops(r, data["cell"]) for r in runs)
    seconds = sum(r.t1 - r.t0 for r in data["runs"])
    peak = data["peak"]["bf16_flops"] * data["chips"]
    return 100.0 * total / (seconds * peak)
