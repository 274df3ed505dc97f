"""Model FLOPs served in the window over window x chips x bf16 peak (%)."""
from chipbench import readers


def read(run):
    return readers.mfu_wall(run)
