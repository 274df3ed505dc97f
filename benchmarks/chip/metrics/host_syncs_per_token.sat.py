"""Host-blocking device-to-host reads per emitted token (serve stats)."""


def read(run):
    return run.stats["host_syncs_per_token"]
