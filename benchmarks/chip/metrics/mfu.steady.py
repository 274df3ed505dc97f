"""Model FLOPs of the traced serve over its device-busy seconds x bf16
peak (%): below the knee the wall clock is pinned by the load."""
from chipbench import readers


def read(run):
    return readers.mfu_busy(run)
