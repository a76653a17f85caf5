"""Arithmetic the metric readers share (``metrics/<name>.py``)."""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile (linear interpolation), None for no values."""
    v = [x for x in values if x is not None]
    return float(np.percentile(v, q)) if v else None


def roofline_pct(flops: float, nbytes: float, seconds: float,
                 peaks: dict) -> Optional[float]:
    """The least time the chip could take for the work (the larger of
    operations over peak FLOP/s and bytes over peak bandwidth) as a share
    of the time measured, in percent. None where nothing was measured."""
    if seconds <= 0 or (flops <= 0 and nbytes <= 0):
        return None
    bound = max(flops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * bound / seconds


def idle_pct(obs: dict) -> Optional[float]:
    red = obs.get("trace")
    if not red or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])


def serve_mfu_pct(obs: dict) -> Optional[float]:
    """Model operations of the traced steps over the traced window at
    the chip's bf16 peak, in percent."""
    from benchmarks.chip.counts import dense_lm_step as step

    red, w = obs.get("trace"), obs.get("traced_work")
    if not red or not w or red["window_s"] <= 0:
        return None
    flops = (step.prefill_flops(obs["config"], w["prefill_tokens"],
                                w["prefill_sq"], w["prefills"])
             + step.decode_flops(obs["config"], w["decode_tokens"],
                                 w["decode_context"]))
    if flops <= 0:
        return None
    return 100.0 * flops / (red["window_s"]
                            * obs["peaks"]["bf16_flops_per_s"])
