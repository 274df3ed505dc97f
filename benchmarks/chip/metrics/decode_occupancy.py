"""Decode tokens over decode-step slots in the window (%)."""
from chipbench import readers


def read(run):
    return readers.decode_occupancy(run)
