"""Seconds of Server.warmup: every shape the cell uses, compiled or loaded
from the persistent cache."""


def read(run):
    return run.timings["warmup_compile_s"]
