"""Output tokens emitted inside the window over the window (tokens/s)."""
from chipbench import readers


def read(run):
    return readers.tokens_in_window(run) / run.seconds
