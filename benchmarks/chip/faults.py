"""Faults planted in the program under test, to show that the check
which decides ``correct`` catches them. Each ``plant`` returns a
function that takes the fault out again. Only the tests and
``control.py`` plant faults; the benchmark's own runs never do.

- ``token``: every token the engine samples is altered where it is
  produced (the next id up);
- ``stale_state``: the decode step returns its cache unchanged.
"""
from __future__ import annotations

from typing import Callable


def plant(name: str) -> Callable[[], None]:
    if name == "token":
        import repro.serve.engine as mod

        attr, real = "sample_tokens", mod.sample_tokens

        def fake(logits, key, temperature=0.0):
            return (real(logits, key, temperature) + 1) % logits.shape[-1]
    elif name == "stale_state":
        import repro.serve.engine as mod

        attr, real = "decode_step", mod.decode_step

        def fake(params, cfg, tokens, cache, *a, **k):
            logits, _ = real(params, cfg, tokens, cache, *a, **k)
            return logits, cache
    else:
        raise ValueError(f"unknown fault {name!r}")
    setattr(mod, attr, fake)
    return lambda: setattr(mod, attr, real)
