"""Arithmetic shared by the metric readers in ``metrics/``: each reader is
one call into here, so a metric's definition sits in one visible place."""
from __future__ import annotations

from typing import Optional

import numpy as np

from chipbench import work

# Kernel ops as the device trace names them: the HLO text of the Pallas
# custom call, named after its jitted wrapper.
PSI_MATMUL = r"^%psi_matmul_(int8|packed)[.\d]* = "
PAGED_ATTENTION = r"^%paged_attention_pallas[.\d]* = "


def pct(values, q: float) -> Optional[float]:
    v = np.asarray(values, np.float64)
    return float(np.percentile(v, q)) if v.size else None


def ttft_pct(run, q: float) -> Optional[float]:
    """Percentile of TTFT over the requests due in the window; one that
    never emitted waits forever."""
    v = [r.ttft_s if r.first_token_s is not None else np.inf
         for r in run.requests if r.arrival_s <= run.seconds]
    return pct(v, q)


def queue_pct(run, q: float) -> Optional[float]:
    v = [r.queue_s if r.admit_s is not None else np.inf
         for r in run.requests if r.arrival_s <= run.seconds]
    return pct(v, q)


def tokens_in_window(run) -> int:
    return sum(int(np.sum(np.asarray(r.token_s) <= run.seconds))
               for r in run.requests)


def decode_occupancy(run) -> Optional[float]:
    """Decode tokens over decode-step slots in the window, in %."""
    dec, _ = run.steps(run.seconds)
    if not dec:
        return None
    return 100.0 * sum(len(d) for d in dec) / (len(dec) * run.slots)


def mfu_wall(run) -> Optional[float]:
    """Model FLOPs served in the window over the window's bf16 peak, %."""
    if run.peaks is None:
        return None
    return 100.0 * run.model_flops(run.seconds) / (
        run.seconds * run.cell.chips * run.peaks["bf16_flops"])


def mfu_busy(run) -> Optional[float]:
    """Model FLOPs of the whole traced serve over its device-busy
    seconds' bf16 peak, %."""
    if run.trace is None or run.peaks is None or run.trace.busy_s <= 0:
        return None
    return 100.0 * run.model_flops() / (run.trace.busy_s
                                        * run.peaks["bf16_flops"])


def psi_matmul_roofline(run) -> Optional[float]:
    """Least time of every ``psi_matmul`` call the traced serve required
    over the kernel's summed device time, %."""
    spent = run.kernel_s(PSI_MATMUL)
    if not spent or run.peaks is None:
        return None
    dec, pre = run.steps()
    steps = ([("decode", len(d)) for d in dec]
             + [("prefill", sum(p), len(p)) for p in pre])
    return 100.0 * work.psi_matmul_least_time(run.arch, steps,
                                              run.peaks) / spent


def paged_attention_roofline(run) -> Optional[float]:
    spent = run.kernel_s(PAGED_ATTENTION)
    if not spent or run.peaks is None:
        return None
    dec, _ = run.steps()
    return 100.0 * work.paged_attention_least_time(run.arch, dec,
                                                   run.peaks) / spent


def idle_share(run) -> Optional[float]:
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
