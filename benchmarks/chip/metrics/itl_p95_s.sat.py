"""95th percentile of every inter-token gap that ends in the window,
pooled over requests."""
from chipbench import readers


def read(run):
    return readers.pct(run.window_gaps(), 95)
