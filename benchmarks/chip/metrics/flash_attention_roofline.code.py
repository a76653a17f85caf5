"""The prefill attention kernel's share of its roofline: the least time
the chip needs for the causal attention of the prompts the traced steps
prefilled (``counts/flash_attention.py``, at the configuration's dtype,
true prompt lengths) over the device time of the kernel's ops
(``%flash_attention.<n>``), in percent. Padding to the prompt bucket is
time the kernel spends on no needed work."""
from benchmarks.chip.counts import flash_attention
from benchmarks.chip.devtrace import op_seconds
from benchmarks.chip.readers import roofline_pct


def read(obs):
    red, w = obs.get("trace"), obs.get("traced_work")
    if not red or not w or w["prefill_tokens"] <= 0:
        return None
    secs = op_seconds(red, r"^%flash_attention\.\d+$")
    flops, nbytes = flash_attention.cost(obs["config"], w["prefill_tokens"],
                                         w["prefill_sq"])
    return roofline_pct(flops, nbytes, secs, obs["peaks"])
