"""Device time of the engine's jitted decode program (``decode_tokens``)
in the traced window, per decode position it ran (calls times
``steps_per_sync``), in ms."""
from benchmarks.chip.devtrace import module_seconds


def read(obs):
    red = obs.get("trace")
    if not red:
        return None
    secs, calls = module_seconds(red, "jit_decode_tokens")
    if calls <= 0:
        return None
    return 1e3 * secs / (calls * obs["engine"]["steps_per_sync"])
