"""1 - union of device-op intervals over the traced window (%)."""
from chipbench import readers


def read(run):
    return readers.idle_share(run)
