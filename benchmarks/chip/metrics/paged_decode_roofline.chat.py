"""The paged decode kernel's share of its roofline: the least time the
chip needs for the decode attention the traced steps did
(``counts/paged_decode.py``, at the configuration's dtype) over the
device time of the kernel's ops (``%paged_decode.<n>``), in percent."""
from benchmarks.chip.counts import paged_decode
from benchmarks.chip.devtrace import op_seconds
from benchmarks.chip.readers import roofline_pct


def read(obs):
    red, w = obs.get("trace"), obs.get("traced_work")
    if not red or not w or w["decode_tokens"] <= 0:
        return None
    secs = op_seconds(red, r"^%paged_decode\.\d+$")
    flops, nbytes = paged_decode.cost(obs["config"], w["decode_tokens"],
                                      w["decode_context"])
    return roofline_pct(flops, nbytes, secs, obs["peaks"])
