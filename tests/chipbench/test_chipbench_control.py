"""The serving cells' control: the reference computed in fp8 (the step
below the configuration's bf16) must read above the configuration's
limit. On the chip it is read at the cell's own size on the served
prompts and tokens (``benchmarks/chip/control.py``); here at the
published widths with four layers and a smaller vocabulary: at every
position of a 256-token prompt, and through a whole rehearsal of the
chat cell with the control in the program's place, which the harness's
own comparison must find not correct."""
import numpy as np
import pytest

from benchmarks.chip import weights
from benchmarks.chip.reference import dense_lm

import chipbench_tiny as tiny


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_fp8_control_reads_above_the_limit(seed):
    import jax

    hf = tiny.load("configs", "qwen2-1.5b")
    limit = hf["correct"]["max_logit_gap"]
    hf.update(num_hidden_layers=4, vocab_size=8192)
    w = weights.make_dense(hf, seed)
    seq = np.random.default_rng(seed).integers(0, hf["vocab_size"], 256)
    rows = np.arange(256)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(dense_lm.logits_at(w, hf, seq, rows))
        low = np.asarray(dense_lm.logits_at(w, hf, seq, rows, "fp8"))
    gap = ref.max(-1) - ref[rows, low.argmax(-1)]
    assert gap.max() > limit


def test_control_in_the_programs_place_comes_out_not_correct():
    notes = {}

    def log(msg):
        k, _, v = msg.rpartition(": ")
        notes[k] = v

    mix = tiny.serve_mix("chat-steady")
    mix["check_requests"] = 6
    mix["output_tokens"].update(median=16, max=32)
    res = tiny.run_serve("serve-qwen2-1.5b-chat-steady",
                         config=tiny.published_widths(), mix=mix,
                         control=True, log=log)
    gap = res["checks"]["max_logit_gap"]
    assert res["correct"] is False, res
    assert gap["value"] > gap["limit"]
    # The program itself, on the same requests, stays inside the limit.
    assert float(notes["program max_logit_gap"]) < gap["limit"]
