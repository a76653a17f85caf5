"""Paged decode alignment contract: the block-table-walking Pallas kernel
(interpret mode) and the gather oracle must match ref.attention_ref on
each sequence's logically-ordered visible window, across scrambled block
tables, garbage entries past the allocation, head layouts (MHA/GQA),
idle slots, and the layer a stacked (layers, blocks, ...) pool is read
at. A caller holding one layer's pool passes ``pool[None]`` and layer 0."""
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


def _tables(lens, salt=0, num_blocks=NUM_BLOCKS, max_blocks=MAX_BLOCKS,
            bs=BS):
    """Disjoint scrambled block tables; -1 garbage past each allocation."""
    rng = np.random.RandomState(salt)
    perm = list(rng.permutation(num_blocks))
    bt = np.full((len(lens), max_blocks), -1, np.int32)
    for b, L in enumerate(lens):
        nblk = -(-L // bs) if L else 0
        bt[b, :nblk] = [perm.pop() for _ in range(nblk)]
    return bt


def _gathered(kp, bt, b, L):
    """Sequence b's first L tokens in logical order, (L, Hkv, D), from a
    head-major (num_blocks, Hkv, Bs, D) pool."""
    bs = kp.shape[2]
    nblk = -(-L // bs)
    blocks = np.asarray(kp)[bt[b, :nblk]].swapaxes(1, 2)   # (n, Bs, Hkv, D)
    return blocks.reshape(-1, kp.shape[1], kp.shape[3])[:L]


@pytest.mark.parametrize("Hq,Hkv", [(4, 4), (8, 2)])  # MHA, GQA
@pytest.mark.parametrize("L", [1, 15, 16, 17, 64, 127, 128])
def test_paged_decode_matches_ref_window(Hq, Hkv, L):
    B, D = 2, 32
    q, kp, vp = _pool(B, Hq, Hkv, D, salt=L)
    bt = _tables([L] * B, salt=L)
    out = paged_decode(q, kp[None], vp[None], jnp.asarray(bt),
                       jnp.full((B,), L, jnp.int32), 0, interpret=True)
    for b in range(B):
        kc = jnp.asarray(_gathered(kp, bt, b, L))[None]
        vc = jnp.asarray(_gathered(vp, bt, b, L))[None]
        want = ref.attention_ref(q[b:b + 1], kc, vc, causal=True)
        np.testing.assert_allclose(np.asarray(out[b:b + 1]),
                                   np.asarray(want), atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize("Hq,Hkv", [(4, 4), (8, 2)])  # MHA, GQA
@pytest.mark.parametrize("L", [1, 17, 128])
def test_paged_decode_lane_width_matches_ref_window(Hq, Hkv, L):
    """At a 128-lane head dim the kernel copies pages (here the whole
    8-entry table in one grid step) instead of walking one block a step:
    the same windows, the same answers."""
    B, D = 2, 128
    q, kp, vp = _pool(B, Hq, Hkv, D, salt=L + 1)
    bt = _tables([L] * B, salt=L + 1)
    out = paged_decode(q, kp[None], vp[None], jnp.asarray(bt),
                       jnp.full((B,), L, jnp.int32), 0, interpret=True)
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
    out = paged_decode(q, kp[None], vp[None], jnp.asarray(bt),
                       jnp.asarray(lens, jnp.int32), 0, interpret=True)
    assert float(jnp.abs(out[2]).max()) == 0.0  # idle emits exact zeros
    for b, L in enumerate(lens):
        if L == 0:
            continue
        kc = jnp.asarray(_gathered(kp, bt, b, L))[None]
        vc = jnp.asarray(_gathered(vp, bt, b, L))[None]
        want = ref.attention_ref(q[b:b + 1], kc, vc, causal=True)
        np.testing.assert_allclose(np.asarray(out[b:b + 1]),
                                   np.asarray(want), atol=2e-6, rtol=2e-6)


# Qwen2-1.5B decode widths with a table long enough for several compute
# steps: 48 blocks of 32 tokens, 16 pages (512 tokens) per grid step.
QW_BS, QW_MAX_BLOCKS, QW_NUM_BLOCKS = 32, 48, 128


@pytest.mark.parametrize("Hq,Hkv", [(12, 2), (4, 4)])  # GQA, MHA
@pytest.mark.parametrize("lens", [
    [1, 511, 512, 513],     # one token; ending on, and one past, a step edge
    [1536, 0, 1025, 32],    # whole table; idle slot; three steps; one page
])
def test_paged_decode_multi_page_steps(Hq, Hkv, lens):
    """Windows spanning several compute steps, scrambled tables, garbage
    entries past each allocation that name NaN-filled blocks, NaN past
    each window inside its last block, and an idle slot: the kernel
    matches attention_ref on each gathered window, the idle slot emits
    exact zeros, and no NaN reaches the result."""
    from repro.kernels.paged_decode import paged_decode_grid

    B, D = len(lens), 128
    P, steps = paged_decode_grid(QW_MAX_BLOCKS, QW_BS, D, Hkv, jnp.float32)
    assert 1 < P < QW_MAX_BLOCKS and steps == QW_MAX_BLOCKS // P
    salt = sum(lens) + Hkv
    ks = jax.random.split(jax.random.fold_in(KEY, salt), 3)
    q = jax.random.normal(ks[0], (B, 1, Hq, D), jnp.float32)
    shape = (QW_NUM_BLOCKS, Hkv, QW_BS, D)
    kp = np.array(jax.random.normal(ks[1], shape, jnp.float32))
    vp = np.array(jax.random.normal(ks[2], shape, jnp.float32))
    bt = _tables(lens, salt=salt, num_blocks=QW_NUM_BLOCKS,
                 max_blocks=QW_MAX_BLOCKS, bs=QW_BS)
    unused = np.setdiff1d(np.arange(QW_NUM_BLOCKS), bt[bt >= 0])
    for pool in (kp, vp):
        pool[unused] = np.nan
        for b, L in enumerate(lens):
            if L % QW_BS:
                pool[bt[b, L // QW_BS], :, L % QW_BS:] = np.nan
    bt[bt < 0] = unused[0]
    out = paged_decode(q, jnp.asarray(kp)[None], jnp.asarray(vp)[None],
                       jnp.asarray(bt), jnp.asarray(lens, jnp.int32), 0,
                       interpret=True)
    assert np.isfinite(np.asarray(out)).all()
    for b, L in enumerate(lens):
        if L == 0:
            assert float(jnp.abs(out[b]).max()) == 0.0
            continue
        kc = jnp.asarray(_gathered(kp, bt, b, L))[None]
        vc = jnp.asarray(_gathered(vp, bt, b, L))[None]
        want = ref.attention_ref(q[b:b + 1], kc, vc, causal=True)
        np.testing.assert_allclose(np.asarray(out[b:b + 1]),
                                   np.asarray(want), atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize("layer", ["first", "last"])
@pytest.mark.parametrize("dtype", ["f32", "bf16"])
@pytest.mark.parametrize("D", [64, 112, 128])   # walk, walk, multi-page
def test_paged_decode_stacked_pool_reads_its_layer(D, dtype, layer):
    """A stacked pool of 3 layers read at layer 0 and at the last layer,
    float32 and bf16 pools, the one-block walk (head dims 64, 112) and
    the multi-page steps (128): the kernel matches the gather oracle on
    that layer, the idle slot emits exact zeros, and every other layer,
    all NaN, never reaches the result."""
    L, Hq, Hkv = 3, 4, 2
    lens = [1, 511, 0, 1025]             # one token; a step edge; idle
    li = 0 if layer == "first" else L - 1
    dt = {"f32": jnp.float32, "bf16": jnp.bfloat16}[dtype]
    B = len(lens)
    ks = jax.random.split(jax.random.fold_in(KEY, D + li), 3)
    q = jax.random.normal(ks[0], (B, 1, Hq, D), dt)
    shape = (L, QW_NUM_BLOCKS, Hkv, QW_BS, D)
    pools = []
    for k in ks[1:]:
        pool = np.full(shape, np.nan, np.float32)
        pool[li] = np.asarray(jax.random.normal(k, shape[1:], jnp.float32))
        pools.append(jnp.asarray(pool, dt))
    kp, vp = pools
    bt = jnp.asarray(_tables(lens, salt=D, num_blocks=QW_NUM_BLOCKS,
                             max_blocks=QW_MAX_BLOCKS, bs=QW_BS))
    lengths = jnp.asarray(lens, jnp.int32)
    out = paged_decode(q, kp, vp, bt, lengths, li, interpret=True)
    want = ref.paged_decode_ref(q, kp, vp, bt, lengths, li)
    assert np.isfinite(np.asarray(out, np.float32)).all()
    assert float(jnp.abs(out[2]).max()) == 0.0
    tol = 2e-5 if dtype == "f32" else 1e-2
    live = [b for b, n in enumerate(lens) if n]
    np.testing.assert_allclose(np.asarray(out, np.float32)[live],
                               np.asarray(want, np.float32)[live],
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("max_blocks,Bs,D,Hkv,itemsize", [
    (128, 32, 128, 2, 2),    # Qwen2-1.5B serving pool, bf16
    (128, 32, 128, 2, 4),
    (64, 64, 64, 4, 2),      # TinyLlama head dim: the one-block walk
    (256, 16, 112, 32, 4),   # Zamba2 shared attention: the one-block walk
    (256, 16, 128, 32, 4),   # 32 kv heads at 128 lanes: VMEM-capped
    (8, 16, 32, 2, 4),       # the tests' tiny pool: the one-block walk
    (7, 128, 128, 8, 2),     # prime table: one page per step
    (1, 32, 128, 2, 2),
])
def test_pages_per_step_divides_and_respects_caps(max_blocks, Bs, D, Hkv,
                                                  itemsize):
    """paged_decode_grid: at 128-lane head dims, the largest divisor of
    max_blocks within the token and VMEM caps; any other head dim walks
    one block of one kv head a step."""
    from repro.kernels.paged_decode import (_KV_VMEM_BYTES, _STEP_TOKENS,
                                            paged_decode_grid)

    dtype = {2: jnp.bfloat16, 4: jnp.float32}[itemsize]
    P, steps = paged_decode_grid(max_blocks, Bs, D, Hkv, dtype)
    if D % 128:
        assert (P, steps) == (1, Hkv * max_blocks)
        return
    assert P >= 1 and max_blocks % P == 0 and steps == max_blocks // P
    page_bytes = Hkv * Bs * D * itemsize
    assert P == 1 or P * Bs <= _STEP_TOKENS
    assert P == 1 or 2 * 2 * P * page_bytes <= _KV_VMEM_BYTES
    # The largest such divisor: the next one up breaks a cap.
    bigger = [p for p in range(P + 1, max_blocks + 1) if max_blocks % p == 0]
    if bigger:
        p = bigger[0]
        assert p * Bs > _STEP_TOKENS or 4 * p * page_bytes > _KV_VMEM_BYTES
    if (max_blocks, Bs, D, Hkv) == (128, 32, 128, 2):
        assert P == 16
    if (max_blocks, Bs, D, Hkv) == (256, 16, 128, 32):
        assert P == 4     # 4 x 4 x 256 KiB pages fill the 4 MiB budget


@pytest.mark.parametrize("D", [64, 128])
def test_paged_decode_live_steps_follow_windows(D):
    """Live grid steps: a window reads ceil(window / (P * Bs)) steps of
    every kv head at once, or ceil(window / Bs) blocks of each kv head on
    the one-block walk; idle slots read none."""
    from repro.kernels.paged_decode import (paged_decode_grid,
                                            paged_decode_live_steps)

    args = (48, 32, D, 2, jnp.float32)
    P, steps = paged_decode_grid(*args)
    windows = np.array([[0, 1, 32, 33], [511, 512, 513, 1536]])
    per_step = P * 32
    want = sum(-(-int(w) // per_step) for w in windows.ravel())
    if D % 128:
        want *= 2
    assert paged_decode_live_steps(windows, *args) == want
    assert paged_decode_live_steps(np.zeros((3, 4), int), *args) == 0
    assert paged_decode_live_steps(np.full(4, 1536), *args) == 4 * steps


def test_paged_ref_oracle_matches_contiguous_gather():
    """The gather oracle (what CPU serving runs) equals decode_ref on a
    cache rebuilt in logical order."""
    q, kp, vp = _pool(3, 8, 2, 32, salt=7)
    lens = [7, 33, 128]
    bt = _tables(lens, salt=7)
    out = ref.paged_decode_ref(q, kp[None], vp[None], jnp.asarray(bt),
                               jnp.asarray(lens, jnp.int32), 0)
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
    out = paged_decode(q, kp[None], vp[None], jnp.asarray(bt),
                       jnp.asarray(lens, jnp.int32), 0, interpret=True)
    for b, L in enumerate(lens):
        kc = jnp.asarray(_gathered(kp, bt, b, L))[None]
        vc = jnp.asarray(_gathered(vp, bt, b, L))[None]
        want = ref.attention_ref(q[b:b + 1], kc, vc, causal=True)
        np.testing.assert_allclose(np.asarray(out[b:b + 1]),
                                   np.asarray(want), atol=2e-6, rtol=2e-6)


def test_ops_attention_routes_paged_impls():
    """ops.attention with block_tables: every impl spelling lands on a
    table-walking path (kernel or oracle), and they agree; the window
    mask / table walk can never be dropped; a decode names its layer."""
    q, kp, vp = _pool(2, 4, 2, 32, salt=21)
    kp, vp = kp[None], vp[None]
    lens = jnp.asarray([20, 100], jnp.int32)
    bt = jnp.asarray(_tables([20, 100], salt=21))
    kw = dict(lengths=lens, block_tables=bt, layer=0)
    o_kernel = ops.attention(q, kp, vp, impl="pallas_interpret", **kw)
    o_ref = ops.attention(q, kp, vp, impl="ref", **kw)
    o_auto = ops.attention(q, kp, vp, impl="auto", **kw)
    o_decode = ops.attention(q, kp, vp, impl="decode_ref", **kw)
    np.testing.assert_allclose(np.asarray(o_kernel), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(o_auto), np.asarray(o_ref))
    np.testing.assert_array_equal(np.asarray(o_decode), np.asarray(o_ref))
    with pytest.raises(ValueError):
        ops.attention(q, kp, vp, block_tables=bt)  # tables need lengths
    with pytest.raises(ValueError):                 # a decode needs a layer
        ops.attention(q, kp, vp, lengths=lens, block_tables=bt)


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
    o_dec = ref.paged_decode_ref(q, kp[None], vp[None], jnp.asarray(bt),
                                 lens, 0)
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
