"""90th percentile of the time to first token, from each request's due
time, over every request that arrived in the window; a request that never
emitted counts as an infinite wait."""
from chipbench import readers


def read(run):
    return readers.ttft_pct(run, 90)
