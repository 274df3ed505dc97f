"""Paged decode attention: least time of the reads served (each active
slot's real K/V once, 4*ctx*Hq*D at the bf16 peak) over the kernel time (%)."""
from chipbench import readers


def read(run):
    return readers.paged_attention_roofline(run)
