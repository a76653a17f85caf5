"""Paged decode alignment contract: the block-table-walking Pallas kernel
(interpret mode) and the gather oracle must match ref.attention_ref on
each sequence's logically-ordered visible window, across scrambled block
tables, garbage entries past the allocation, head layouts (MHA/GQA), and
idle slots."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.paged_decode import paged_decode

KEY = jax.random.key(11)
BS = 16          # pool block size (tokens)
MAX_BLOCKS = 8   # logical blocks per sequence (max_seq = 128)
NUM_BLOCKS = 40  # physical pool blocks


def _pool(B, Hq, Hkv, D, dtype=jnp.float32, salt=0):
    ks = jax.random.split(jax.random.fold_in(KEY, salt), 3)
    q = jax.random.normal(ks[0], (B, 1, Hq, D), dtype)
    kp = jax.random.normal(ks[1], (NUM_BLOCKS, Hkv, BS, D), dtype)
    vp = jax.random.normal(ks[2], (NUM_BLOCKS, Hkv, BS, D), dtype)
    return q, kp, vp


def _tables(lens, salt=0):
    """Disjoint scrambled block tables; -1 garbage past each allocation."""
    rng = np.random.RandomState(salt)
    perm = list(rng.permutation(NUM_BLOCKS))
    bt = np.full((len(lens), MAX_BLOCKS), -1, np.int32)
    for b, L in enumerate(lens):
        nblk = -(-L // BS) if L else 0
        bt[b, :nblk] = [perm.pop() for _ in range(nblk)]
    return bt


def _gathered(kp, bt, b, L):
    """Sequence b's first L tokens in logical order, (L, Hkv, D), from a
    head-major (num_blocks, Hkv, BS, D) pool."""
    nblk = -(-L // BS)
    blocks = np.asarray(kp)[bt[b, :nblk]].swapaxes(1, 2)   # (n, BS, Hkv, D)
    return blocks.reshape(-1, kp.shape[1], kp.shape[3])[:L]


@pytest.mark.parametrize("Hq,Hkv", [(4, 4), (8, 2)])  # MHA, GQA
@pytest.mark.parametrize("L", [1, 15, 16, 17, 64, 127, 128])
def test_paged_decode_matches_ref_window(Hq, Hkv, L):
    B, D = 2, 32
    q, kp, vp = _pool(B, Hq, Hkv, D, salt=L)
    bt = _tables([L] * B, salt=L)
    out = paged_decode(q, kp, vp, jnp.asarray(bt),
                       jnp.full((B,), L, jnp.int32), interpret=True)
    for b in range(B):
        kc = jnp.asarray(_gathered(kp, bt, b, L))[None]
        vc = jnp.asarray(_gathered(vp, bt, b, L))[None]
        want = ref.attention_ref(q[b:b + 1], kc, vc, causal=True)
        np.testing.assert_allclose(np.asarray(out[b:b + 1]),
                                   np.asarray(want), atol=2e-6, rtol=2e-6)


def test_paged_decode_mixed_lengths_and_idle():
    q, kp, vp = _pool(4, 8, 2, 64, salt=99)
    lens = [1, 40, 0, 128]               # slot 2 idle
    bt = _tables(lens, salt=99)
    out = paged_decode(q, kp, vp, jnp.asarray(bt),
                       jnp.asarray(lens, jnp.int32), interpret=True)
    assert float(jnp.abs(out[2]).max()) == 0.0  # idle emits exact zeros
    for b, L in enumerate(lens):
        if L == 0:
            continue
        kc = jnp.asarray(_gathered(kp, bt, b, L))[None]
        vc = jnp.asarray(_gathered(vp, bt, b, L))[None]
        want = ref.attention_ref(q[b:b + 1], kc, vc, causal=True)
        np.testing.assert_allclose(np.asarray(out[b:b + 1]),
                                   np.asarray(want), atol=2e-6, rtol=2e-6)


def test_paged_ref_oracle_matches_contiguous_gather():
    """The gather oracle (what CPU serving runs) equals decode_ref on a
    cache rebuilt in logical order."""
    q, kp, vp = _pool(3, 8, 2, 32, salt=7)
    lens = [7, 33, 128]
    bt = _tables(lens, salt=7)
    out = ref.paged_decode_ref(q, kp, vp, jnp.asarray(bt),
                               jnp.asarray(lens, jnp.int32))
    for b, L in enumerate(lens):
        kc = jnp.asarray(_gathered(kp, bt, b, L))[None]
        vc = jnp.asarray(_gathered(vp, bt, b, L))[None]
        want = ref.attention_ref(q[b:b + 1], kc, vc, causal=True)
        np.testing.assert_allclose(np.asarray(out[b:b + 1]),
                                   np.asarray(want), atol=2e-5, rtol=2e-5)


def test_shared_prefix_blocks_between_sequences():
    """Two sequences may alias the same physical blocks (post-fork shared
    prefix): both must read the shared content correctly."""
    q, kp, vp = _pool(2, 4, 4, 32, salt=3)
    bt = np.full((2, MAX_BLOCKS), -1, np.int32)
    bt[0, :2] = [5, 9]
    bt[1, :3] = [5, 9, 17]               # shares blocks 5, 9 with seq 0
    lens = [32, 40]
    out = paged_decode(q, kp, vp, jnp.asarray(bt),
                       jnp.asarray(lens, jnp.int32), interpret=True)
    for b, L in enumerate(lens):
        kc = jnp.asarray(_gathered(kp, bt, b, L))[None]
        vc = jnp.asarray(_gathered(vp, bt, b, L))[None]
        want = ref.attention_ref(q[b:b + 1], kc, vc, causal=True)
        np.testing.assert_allclose(np.asarray(out[b:b + 1]),
                                   np.asarray(want), atol=2e-6, rtol=2e-6)


def test_ops_attention_routes_paged_impls():
    """ops.attention with block_tables: every impl spelling lands on a
    table-walking path (kernel or oracle), and they agree; the window
    mask / table walk can never be dropped."""
    q, kp, vp = _pool(2, 4, 2, 32, salt=21)
    lens = jnp.asarray([20, 100], jnp.int32)
    bt = jnp.asarray(_tables([20, 100], salt=21))
    o_kernel = ops.attention(q, kp, vp, lengths=lens, block_tables=bt,
                             impl="pallas_interpret")
    o_ref = ops.attention(q, kp, vp, lengths=lens, block_tables=bt,
                          impl="ref")
    o_auto = ops.attention(q, kp, vp, lengths=lens, block_tables=bt,
                           impl="auto")
    o_decode = ops.attention(q, kp, vp, lengths=lens, block_tables=bt,
                             impl="decode_ref")
    np.testing.assert_allclose(np.asarray(o_kernel), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(o_auto), np.asarray(o_ref))
    np.testing.assert_array_equal(np.asarray(o_decode), np.asarray(o_ref))
    with pytest.raises(ValueError):
        ops.attention(q, kp, vp, block_tables=bt)  # tables need lengths


# ----------------------------------------------------- chunked prefill
def _prefill_case(B, Sq, Hq, Hkv, D, offs, true_lens, salt):
    ks = jax.random.split(jax.random.fold_in(KEY, salt), 3)
    q = jax.random.normal(ks[0], (B, Sq, Hq, D), jnp.float32)
    kp = jax.random.normal(ks[1], (NUM_BLOCKS, Hkv, BS, D), jnp.float32)
    vp = jax.random.normal(ks[2], (NUM_BLOCKS, Hkv, BS, D), jnp.float32)
    lens = [o + t for o, t in zip(offs, true_lens)]
    bt = _tables(lens, salt=salt)
    return q, kp, vp, bt, np.asarray(lens, np.int32), np.asarray(
        offs, np.int32)


@pytest.mark.parametrize("Hq,Hkv", [(4, 4), (8, 2)])  # MHA, GQA
@pytest.mark.parametrize("Sq,offs", [
    (5, [0, 8]),      # chunk from scratch / block-aligned offset
    (8, [3, 16]),     # mid-block offset / chunk == block size
    (1, [7, 0]),      # 1-token chunk (budget smaller than one block)
    (16, [16, 40]),   # chunk spanning multiple blocks
])
def test_paged_prefill_matches_ref(Hq, Hkv, Sq, offs):
    """paged_prefill kernel == gather oracle == attention_ref composed on
    each sequence's gathered visible window (queries [s, e) vs keys
    [0, e), causal by absolute position)."""
    B, D = 2, 32
    true_lens = [Sq, Sq]
    q, kp, vp, bt, lens, qoff = _prefill_case(
        B, Sq, Hq, Hkv, D, offs, true_lens, salt=Sq * 31 + offs[0]
    )
    from repro.kernels.paged_decode import paged_prefill

    o_k = paged_prefill(q, kp, vp, jnp.asarray(bt), jnp.asarray(lens),
                        jnp.asarray(qoff), interpret=True)
    o_r = ref.paged_prefill_ref(q, kp, vp, jnp.asarray(bt),
                                jnp.asarray(lens), jnp.asarray(qoff))
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               atol=2e-5, rtol=2e-5)
    for b in range(B):
        e = int(lens[b])
        kc = jnp.asarray(_gathered(kp, bt, b, e))[None]
        vc = jnp.asarray(_gathered(vp, bt, b, e))[None]
        want = ref.attention_ref(q[b:b + 1], kc, vc, causal=True)
        np.testing.assert_allclose(np.asarray(o_k[b:b + 1]),
                                   np.asarray(want), atol=2e-5, rtol=2e-5)


def test_paged_prefill_padded_tail_is_harmless():
    """Bucket-padded tail queries (true chunk shorter than Sq) produce
    garbage the caller discards — but the REAL rows must be exact and
    finite everywhere (no NaN from fully-masked rows)."""
    B, Sq, Hq, Hkv, D = 2, 8, 4, 2, 32
    true_lens = [5, 3]
    offs = [8, 0]
    q, kp, vp, bt, lens, qoff = _prefill_case(
        B, Sq, Hq, Hkv, D, offs, true_lens, salt=77
    )
    from repro.kernels.paged_decode import paged_prefill

    o_k = paged_prefill(q, kp, vp, jnp.asarray(bt), jnp.asarray(lens),
                        jnp.asarray(qoff), interpret=True)
    o_r = ref.paged_prefill_ref(q, kp, vp, jnp.asarray(bt),
                                jnp.asarray(lens), jnp.asarray(qoff))
    assert np.isfinite(np.asarray(o_k)).all()
    for b in range(B):
        np.testing.assert_allclose(
            np.asarray(o_k[b, : true_lens[b]]),
            np.asarray(o_r[b, : true_lens[b]]), atol=2e-5, rtol=2e-5,
        )


def test_ops_attention_routes_paged_prefill():
    """q_offset (or Sq > 1 with tables) routes every impl spelling to a
    chunked-prefill path; Sq > 1 without q_offset is an error, as is
    lengths with Sq > 1 and no tables."""
    B, Sq, Hq, Hkv, D = 2, 4, 4, 2, 32
    q, kp, vp, bt, lens, qoff = _prefill_case(
        B, Sq, Hq, Hkv, D, [8, 3], [Sq, Sq], salt=13
    )
    kw = dict(lengths=jnp.asarray(lens), block_tables=jnp.asarray(bt),
              q_offset=jnp.asarray(qoff))
    o_ref = ops.attention(q, kp, vp, impl="ref", **kw)
    o_kernel = ops.attention(q, kp, vp, impl="pallas_interpret", **kw)
    o_auto = ops.attention(q, kp, vp, impl="auto", **kw)
    o_dec = ops.attention(q, kp, vp, impl="decode_ref", **kw)
    np.testing.assert_allclose(np.asarray(o_kernel), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(o_auto), np.asarray(o_ref))
    np.testing.assert_array_equal(np.asarray(o_dec), np.asarray(o_ref))
    with pytest.raises(ValueError):
        ops.attention(q, kp, vp, lengths=jnp.asarray(lens),
                      block_tables=jnp.asarray(bt))   # Sq>1 needs q_offset
    with pytest.raises(ValueError):
        ops.attention(q, kp, vp, lengths=jnp.asarray(lens))  # no tables


def test_paged_prefill_q_offset_one_token_equals_decode():
    """A 1-token chunk at offset L-1 computes the same attention as a
    decode step at cache length L-1 (window L): the two kernels must
    agree on their shared boundary case."""
    B, Hq, Hkv, D = 2, 4, 2, 32
    L = [21, 64]
    q, kp, vp = _pool(B, Hq, Hkv, D, salt=5)
    bt = _tables(L, salt=5)
    lens = jnp.asarray(L, jnp.int32)
    o_dec = ref.paged_decode_ref(q, kp, vp, jnp.asarray(bt), lens)
    o_pre = ref.paged_prefill_ref(q, kp, vp, jnp.asarray(bt), lens,
                                  lens - 1)
    np.testing.assert_allclose(np.asarray(o_dec), np.asarray(o_pre),
                               atol=2e-5, rtol=2e-5)


def test_paged_block_kv_table():
    from repro.core.autotune import paged_block_kv

    assert paged_block_kv(4096, 64) == 64
    assert paged_block_kv(4096, 128) == 32
    assert paged_block_kv(4096, 256) == 16
    assert paged_block_kv(32, 64) == 32      # clamped to the cache cap
    bk = paged_block_kv(96, 64)              # non-power-of-two cap
    assert 96 % bk == 0
