"""One minus the union of the chip's op intervals over the traced
window, in percent."""
from benchmarks.chip.readers import idle_pct


def read(obs):
    return idle_pct(obs)
