"""Model operations of the prefill and decode work done in the traced
window (``counts/dense_lm_step.py``), over the window at the chip's
bf16 peak, in percent."""
from benchmarks.chip.readers import serve_mfu_pct


def read(obs):
    return serve_mfu_pct(obs)
