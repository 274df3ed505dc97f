"""Reduction of a profiler trace (``.xplane.pb``) to device numbers.

Reads the trace with ``jax.profiler.ProfileData`` and takes, for each chip,
the operations on its "XLA Ops" line: their union is the busy time, a
kernel's time is the sum of the durations of the ops whose name (their HLO
text) matches the kernel's pattern, and the gaps between operations are the
idle time, each named by the programs on either side of it (from the "XLA
Modules" line).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"


# ops that contain other ops on the same line (a scanned layer loop)
CONTAINERS = re.compile(r"^%?(while|conditional|call)[.\d]* ")


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Device:
    name: str
    ops: List[Event]
    modules: List[Event]


def devices_from_profile(profile, prefix: str = DEVICE_PREFIX
                         ) -> List[Device]:
    """The device planes of a ``ProfileData`` whose name starts with
    ``prefix``, each with its op and module events."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith(prefix):
            continue
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines:
            continue
        ops = [Event(e.name, e.start_ns, e.duration_ns)
               for e in lines[OPS_LINE].events]
        mods = ([Event(e.name, e.start_ns, e.duration_ns)
                 for e in lines[MODULES_LINE].events]
                if MODULES_LINE in lines else [])
        out.append(Device(plane.name, ops, mods))
    return out


def load(log_dir: str) -> List[Device]:
    """Every TPU device of the newest ``.xplane.pb`` under ``log_dir``."""
    import jax
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return devices_from_profile(jax.profiler.ProfileData.from_file(paths[-1]))


def union_ns(intervals: Sequence[Tuple[float, float]]) -> float:
    """Length of the union of [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_s(dev: Device) -> float:
    return union_ns([(e.start_ns, e.end_ns) for e in dev.ops]) * 1e-9


def kernel_s(dev: Device, pattern: str) -> float:
    """Summed device seconds of the ops whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(e.dur_ns for e in dev.ops if rx.search(e.name)) * 1e-9


def short_name(name: str) -> str:
    """An op event's HLO text cut to its instruction name and result type,
    e.g. ``%psi_matmul_int8.70 = bf16[32,12288]``."""
    head, _, rest = name.partition(" = ")
    return f"{head} = {rest.split('{')[0].split(' ')[0]}" if rest else head


def top_ops(devs: Sequence[Device], n: int = 10) -> List[List]:
    """The ``n`` ops with the most device time, averaged over chips; ops
    that contain others (a layer loop) are left out, their body counts."""
    acc: Dict[str, float] = {}
    for dev in devs:
        for e in dev.ops:
            if CONTAINERS.match(e.name):
                continue
            k = short_name(e.name)
            acc[k] = acc.get(k, 0.0) + e.dur_ns * 1e-9
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / len(devs)] for k, v in ranked]


def _module_names(mods: List[Event]):
    """Module names with their sorted ends and starts, for bisection (the
    programs of one chip run one after another)."""
    mods = sorted(mods, key=lambda m: m.start_ns)
    return ([_short(m.name) for m in mods], [m.end_ns for m in mods],
            [m.start_ns for m in mods])


def _module_before(index, t: float) -> str:
    names, ends, _ = index
    i = bisect.bisect_right(ends, t) - 1
    return names[i] if i >= 0 else "none"


def _module_after(index, t: float) -> str:
    names, _, starts = index
    i = bisect.bisect_left(starts, t)
    return names[i] if i < len(names) else "none"


def _short(name: str) -> str:
    m = re.search(r"jit_([A-Za-z0-9_]+)", name)
    return m.group(1) if m else name.split("(")[0]


def idle_gaps(devs: Sequence[Device], n: int = 10) -> List[List]:
    """Idle time between device ops summed by the programs around each gap
    (``"<program before> -> <program after>"``), the ``n`` largest, in
    seconds averaged over chips."""
    acc: Dict[str, float] = {}
    for dev in devs:
        ivs = sorted((e.start_ns, e.end_ns) for e in dev.ops)
        index = _module_names(dev.modules)
        end = None
        for s, e in ivs:
            if end is not None and s > end:
                key = (f"{_module_before(index, end)} -> "
                       f"{_module_after(index, s)}")
                acc[key] = acc.get(key, 0.0) + (s - end) * 1e-9
            end = e if end is None else max(end, e)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / len(devs)] for k, v in ranked]


@dataclasses.dataclass
class Reduced:
    busy_s: float
    window_s: float
    device_ops: List[List]
    idle_gaps: List[List]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def reduce(devs: Sequence[Device], window_s: float) -> Reduced:
    """Busy seconds averaged over the chips, over a traced window of
    ``window_s`` seconds, with the top ops and idle gaps."""
    if not devs:
        raise ValueError("the trace holds no TPU device plane")
    return Reduced(
        busy_s=sum(busy_s(d) for d in devs) / len(devs),
        window_s=window_s, device_ops=top_ops(devs),
        idle_gaps=idle_gaps(devs))
