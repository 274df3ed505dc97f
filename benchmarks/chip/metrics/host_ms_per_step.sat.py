"""Host work per decode step (ms): the served window less the loop's
blocking device reads and arrival sleeps (serve stats ``host_busy_s``,
timed at the bounds of its ``*.wait`` and ``idle.sleep`` spans), over the
decode steps.  A program without that counter reads nothing."""


def read(run):
    busy = run.stats.get("host_busy_s")
    steps = run.stats.get("decode_steps")
    return None if busy is None or not steps else 1e3 * busy / steps
