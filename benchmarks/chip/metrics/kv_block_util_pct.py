"""Peak KV pool blocks in use over the pool (serve stats block_util_pct)."""


def read(run):
    return run.stats.get("block_util_pct")
