"""The plain references against the program, on the CPU at small sizes."""
import numpy as np
import pytest

from benchmarks.chip import weights
from benchmarks.chip.reference import dense_lm

import chipbench_tiny as tiny


def _program_logits(hf, w, tokens):
    import jax.numpy as jnp

    from benchmarks.chip.drivers.serve import model_config
    from repro.models import forward

    cfg = model_config(hf)
    logits, _, _ = forward(weights.program_params(w), cfg,
                           tokens=jnp.asarray(tokens)[None])
    return np.asarray(logits[0, :, :hf["vocab_size"]], np.float32)


@pytest.mark.parametrize("tie", [True, False])
def test_dense_reference_matches_program_forward_f32(tie):
    hf = tiny.dense("float32")
    hf["tie_word_embeddings"] = tie
    w = weights.make_dense(hf, seed=3, dtype="float32")
    toks = np.random.default_rng(0).integers(0, hf["vocab_size"], 40)
    got = _program_logits(hf, w, toks)
    want = np.asarray(dense_lm.logits_at(w, hf, toks, np.arange(40)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_served_gaps_of_greedy_f32_engine_are_zero():
    """The f32 engine's greedy tokens are the reference's best ones."""
    from benchmarks.chip.drivers.serve import model_config
    from repro.serve.engine import Engine, GLBReplicaBalancer, Request

    hf = tiny.dense("float32")
    w = weights.make_dense(hf, seed=5, dtype="float32")
    eng = Engine(model_config(hf), weights.program_params(w), paged=True,
                 max_slots=2, max_seq=128, pad_len=64, steps_per_sync=4)
    bal = GLBReplicaBalancer([eng])
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, prompt=rng.integers(0, hf["vocab_size"],
                                                n).tolist(), max_new=9)
            for i, n in enumerate((5, 33, 64))]
    for r in reqs:
        bal.submit(r)
    assert bal.run(max_steps=200) == "terminated"
    for r in reqs:
        gaps = dense_lm.served_gaps(w, hf, r.prompt, r.out, 128, 16)
        assert len(gaps) == len(r.out) == 10
        assert gaps.max() < 1e-4, gaps


def test_served_gaps_see_a_wrong_token():
    hf = tiny.dense("float32")
    w = weights.make_dense(hf, seed=5, dtype="float32")
    prompt = list(range(1, 20))
    logits = np.asarray(dense_lm.logits_at(w, hf, np.asarray(prompt),
                                           np.asarray([18])))
    best = int(logits.argmax())
    worst = int(logits.argmin())
    gap = dense_lm.served_gaps(w, hf, prompt, [worst], 64, 4)
    assert gap[0] == pytest.approx(logits.max() - logits.min(), rel=1e-5)
    assert dense_lm.served_gaps(w, hf, prompt, [best], 64, 4)[0] == 0.0


def test_fp8_control_moves_logits_more_than_bf16():
    """The control computes the same forward through fp8: its logits
    stray further from float32 than a bf16 copy of the weights does."""
    import jax.numpy as jnp

    hf = tiny.dense("float32")
    w = weights.make_dense(hf, seed=9, dtype="float32")
    toks = np.random.default_rng(2).integers(0, hf["vocab_size"], 48)
    rows = np.arange(48)
    ref = np.asarray(dense_lm.logits_at(w, hf, toks, rows))
    low = np.asarray(dense_lm.logits_at(w, hf, toks, rows, "fp8"))
    wb = {k: v.astype(jnp.bfloat16).astype(jnp.float32) for k, v in w.items()}
    mid = np.asarray(dense_lm.logits_at(wb, hf, toks, rows))
    assert np.abs(low - ref).max() > 3 * np.abs(mid - ref).max()


def test_weights_same_seed_same_values_and_large_seeds_differ():
    hf = tiny.dense()
    a = weights.make_dense(hf, seed=2 ** 33 + 1)
    b = weights.make_dense(hf, seed=2 ** 33 + 1)
    c = weights.make_dense(hf, seed=1)
    assert np.array_equal(np.asarray(a["q_proj"], np.float32),
                          np.asarray(b["q_proj"], np.float32))
    assert not np.array_equal(np.asarray(a["q_proj"], np.float32),
                              np.asarray(c["q_proj"], np.float32))
    assert str(a["q_proj"].dtype) == "bfloat16"
    vp = weights.padded_vocab(hf["vocab_size"])
    assert a["embed_tokens"].shape == (vp, hf["hidden_size"])
    assert not np.asarray(a["embed_tokens"][hf["vocab_size"]:],
                          np.float32).any()
