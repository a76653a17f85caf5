"""A plain greedy decoding reference for the serving tests.

``greedy_reference`` runs each request alone through ``models.forward``
(prefill) and ``models.decode_step`` on a float32 contiguous cache, with
none of the engine's slots, scheduler, block pool or fused decode loop —
so a test that compares an ``Engine`` against it checks the engine's
bookkeeping against an independent loop, not against itself. The limits
are the engine's: the prompt is truncated to ``pad_len`` (and padded to
it for the prefill), one token is taken from the prefill, then decoding
stops after ``max_new`` tokens or once the cache holds ``max_seq - 1``.
"""
import functools
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import decode_step, forward, make_cache


@functools.lru_cache(maxsize=None)
def _fns(cfg):
    vocab = cfg.vocab

    @jax.jit
    def prefill(params, tokens, cache, true_len):
        logits, cache, _ = forward(params, cfg, tokens=tokens, cache=cache,
                                   cache_len=jnp.int32(0), mode="prefill")
        return jnp.argmax(logits[0, true_len - 1, ..., :vocab]), cache

    @jax.jit
    def decode(params, token, cache, length):
        logits, cache = decode_step(params, cfg, token, cache, length)
        return jnp.argmax(logits[0, 0, ..., :vocab]), cache

    return prefill, decode


def greedy_reference(cfg, params, requests, *, pad_len: int,
                     max_seq: int) -> List[List[int]]:
    """Greedy outputs of ``requests`` (each with ``prompt`` and
    ``max_new``), one list of token ids per request, in order."""
    prefill, decode = _fns(cfg)
    outs = []
    for req in requests:
        prompt = list(req.prompt[:pad_len])
        true_len = len(prompt)
        toks = np.zeros((1, pad_len), np.int32)
        toks[0, :true_len] = prompt
        cache = make_cache(cfg, 1, max_seq, dtype=jnp.float32)
        tok, cache = prefill(params, jnp.asarray(toks), cache, true_len)
        out = [int(tok)]
        length = true_len
        while len(out) - 1 < req.max_new and length < max_seq - 1:
            tok, cache = decode(params, jnp.full((1, 1), out[-1], jnp.int32),
                                cache, jnp.full((1,), length, jnp.int32))
            out.append(int(tok))
            length += 1
        outs.append(out)
    return outs
