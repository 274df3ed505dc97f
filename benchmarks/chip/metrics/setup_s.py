"""The chip in hand to the first due arrival: the program's imports, the
weights, the server, its warm-up and cache loads."""


def read(run):
    return run.timings["setup_s"]
