"""Host spans of the serve loop, read from the profiler trace that holds the
device planes, and the device's idle time put down to them.

``Server.serve`` writes its spans with ``jax.profiler.TraceAnnotation``
into the profiler's own session, so they lie on one host thread's line
(``/host:CPU``), on the clock of the device planes.  The names are the
program's (``SPANS``); the line also holds the profiler's own events,
which are left out.
"""
from __future__ import annotations

import bisect
import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from chipbench import trace

HOST_PREFIX = "/host:CPU"
SPANS = ("serve.window", "serve.iter", "sched.admit", "prefill.dispatch",
         "prefill.wait", "kv.alloc", "decode.dispatch", "decode.wait",
         "decode.emit", "idle.sleep", "gc")
WAITS = ("prefill.wait", "decode.wait")
NO_SPAN = "(no span)"


@dataclasses.dataclass
class Span(trace.Event):
    args: Dict = dataclasses.field(default_factory=dict)


def host_spans(profile) -> List[Span]:
    """The program's spans on the host line of ``profile`` (a
    ``ProfileData``) that holds ``serve.window``, in time order; none when
    no line holds one."""
    for plane in profile.planes:
        if not plane.name.startswith(HOST_PREFIX):
            continue
        for line in plane.lines:
            events = [e for e in line.events if e.name in SPANS]
            if any(e.name == "serve.window" for e in events):
                return sorted((Span(e.name, e.start_ns, e.duration_ns,
                                    dict(e.stats)) for e in events),
                              key=lambda s: (s.start_ns, -s.dur_ns))
    return []


def _innermost(spans: Sequence[Span]) -> List[Tuple[float, float, str]]:
    """The time under the spans other than ``serve.window`` cut into
    pieces ``(start, end, name)``, each named by the innermost span open
    over it (the one opened last)."""
    inner = sorted((s for s in spans if s.name != "serve.window"),
                   key=lambda s: s.start_ns)
    times = sorted({t for s in inner for t in (s.start_ns, s.end_ns)})
    out, active, i = [], [], 0
    for t, t_next in zip(times, times[1:]):
        active = [s for s in active if s.end_ns > t]
        while i < len(inner) and inner[i].start_ns <= t:
            if inner[i].end_ns > t:
                active.append(inner[i])
            i += 1
        if active:
            top = max(active, key=lambda s: (s.start_ns, -s.end_ns))
            out.append((t, t_next, top.name))
    return out


def _idle_in(dev: trace.Device, lo: float, hi: float):
    """The device's idle intervals inside ``[lo, hi)``."""
    end = lo
    for s, e in sorted((e.start_ns, e.end_ns) for e in dev.ops):
        if s >= hi:
            break
        if s > end:
            yield end, s
        end = max(end, e)
    if end < hi:
        yield end, hi


def idle_by_host_span(devs: Sequence[trace.Device], spans: Sequence[Span],
                      n: Optional[int] = 10) -> List[List]:
    """Device idle time inside ``serve.window`` split among the innermost
    host spans over it, by overlap: ``serve.iter`` where an iteration has
    no other span open, ``(no span)`` outside every iteration.  The ``n``
    largest, in seconds averaged over chips."""
    windows = [s for s in spans if s.name == "serve.window"]
    if not windows or not devs:
        return []
    pieces = _innermost(spans)
    starts = [p[0] for p in pieces]
    acc: Dict[str, float] = {}
    for w in windows:
        for dev in devs:
            for a, b in _idle_in(dev, w.start_ns, w.end_ns):
                covered = 0.0
                i = max(bisect.bisect_right(starts, a) - 1, 0)
                while i < len(pieces) and pieces[i][0] < b:
                    s, e, name = pieces[i]
                    ov = min(e, b) - max(s, a)
                    if ov > 0:
                        acc[name] = acc.get(name, 0.0) + ov
                        covered += ov
                    i += 1
                acc[NO_SPAN] = acc.get(NO_SPAN, 0.0) + (b - a) - covered
    ranked = sorted(((k, v) for k, v in acc.items() if v > 0),
                    key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9 / len(devs)] for k, v in ranked]


def host_ms_per_step(spans: Sequence[Span],
                     decode_steps: int) -> Optional[float]:
    """Milliseconds a decode step of the host's work inside ``serve.iter``
    spans, less the time the loop waited (``*.wait``) or slept for an
    arrival (``idle.sleep``): at horizon 1 the device waits on all of it."""
    iters = [s for s in spans if s.name == "serve.iter"]
    if not iters or not decode_steps:
        return None
    off = sorted((s.start_ns, s.end_ns) for s in spans
                 if s.name in WAITS + ("idle.sleep",))
    off_starts = [s for s, _ in off]
    host_ns = 0.0
    for it in iters:
        lo = bisect.bisect_left(off_starts, it.start_ns)
        hi = bisect.bisect_left(off_starts, it.end_ns)
        host_ns += it.dur_ns - trace.union_ns(
            [(max(s, it.start_ns), min(e, it.end_ns))
             for s, e in off[lo:hi]])
    return host_ns * 1e-6 / decode_steps
