"""Device time of the engine's prefill program (``prefill_paged``) in the
traced window, per 1000 prompt tokens the traced steps prefilled."""
from benchmarks.chip.devtrace import module_seconds


def read(obs):
    red, w = obs.get("trace"), obs.get("traced_work")
    if not red or not w or w["prefill_tokens"] <= 0:
        return None
    secs, calls = module_seconds(red, "jit_prefill_paged")
    if calls <= 0:
        return None
    return 1e3 * secs / (w["prefill_tokens"] / 1e3)
