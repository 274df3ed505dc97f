"""90th percentile of the wait from each request's due time to its
admission into a decode slot, over every request that arrived in the
window."""
from chipbench import readers


def read(run):
    return readers.queue_pct(run, 90)
