"""Every Pallas kernel compiles for a TPU v5e chip at real widths.

Interpret mode (the other kernel tests) checks the math but not what the
TPU compiler accepts: block shapes must tile as (8, 128) or cover whole
array dims, and operand layouts must match XLA's. These tests compile each
kernel ahead of time for a *described* v5e:2x2 topology — no chip needed —
at TinyLlama-1.1B (Hq=32, Hkv=4, D=64) and Qwen2-1.5B (Hq=12, Hkv=2,
D=128) attention widths, Mamba2 SSD widths, in bf16 and f32, and check
that the compiled program holds the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at import:
describing it loads the TPU compiler library, which one process at a time
may hold, so only the worker that runs this file loads it.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_decode import flash_decode
from repro.kernels.mamba2_ssd import ssd_chunked
from repro.kernels.moe_gmm import gmm
from repro.kernels.paged_decode import paged_decode, paged_prefill
from repro.kernels.uts_expand import uts_expand
from repro.problems.uts import MAX_CHILD

ATTN_WIDTHS = {"tinyllama": (32, 4, 64), "qwen2": (12, 2, 128)}  # Hq,Hkv,D
DTYPES = {"bf16": jnp.bfloat16, "f32": jnp.float32}
SLOTS, PROMPT, CACHE = 8, 512, 1024
POOL_BLOCKS, BLOCK, TABLE = 128, 64, 16


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache without the chip; keep it out of the cache.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("width", sorted(ATTN_WIDTHS))
@pytest.mark.parametrize("kernel", ["flash_attention", "flash_decode",
                                    "paged_decode", "paged_prefill"])
def test_attention_kernel_compiles_for_v5e(kernel, width, dtype, one_chip):
    Hq, Hkv, D = ATTN_WIDTHS[width]
    dt = DTYPES[dtype]
    i32 = jnp.int32
    pool = ((POOL_BLOCKS, Hkv, BLOCK, D), dt)
    stacked = ((2, POOL_BLOCKS, Hkv, BLOCK, D), dt)     # (layers, ...)
    if kernel == "flash_attention":
        _compile(flash_attention, one_chip, ((1, PROMPT, Hq, D), dt),
                 ((1, PROMPT, Hkv, D), dt), ((1, PROMPT, Hkv, D), dt))
    elif kernel == "flash_decode":
        _compile(flash_decode, one_chip, ((SLOTS, 1, Hq, D), dt),
                 ((SLOTS, CACHE, Hkv, D), dt), ((SLOTS, CACHE, Hkv, D), dt),
                 ((SLOTS,), i32))
    elif kernel == "paged_decode":
        _compile(paged_decode, one_chip, ((SLOTS, 1, Hq, D), dt), stacked,
                 stacked, ((SLOTS, TABLE), i32), ((SLOTS,), i32), ((), i32))
    else:
        _compile(paged_prefill, one_chip, ((1, 200, Hq, D), dt), pool, pool,
                 ((1, TABLE), i32), ((1,), i32), ((1,), i32))


@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_paged_decode_compiles_at_serving_shape(dtype, one_chip):
    """The Qwen2-1.5B serving engine's decode call: 32 slots over a
    stacked pool of 28 layers of 2048 blocks of 32 tokens, 128-entry
    block tables (max_seq 4096), so each slot's table spans several
    multi-page grid steps."""
    dt, Hq, Hkv, D = DTYPES[dtype], *ATTN_WIDTHS["qwen2"]
    pool = ((28, 2048, Hkv, 32, D), dt)
    _compile(paged_decode, one_chip, ((32, 1, Hq, D), dt), pool, pool,
             ((32, 128), jnp.int32), ((32,), jnp.int32), ((), jnp.int32))


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("H,P", [(48, 64), (24, 128)])  # mamba2 headdims
def test_ssd_compiles_for_v5e(H, P, dtype, one_chip):
    dt, N, T = DTYPES[dtype], 128, 512
    _compile(ssd_chunked, one_chip, ((1, T, H, P), dt), ((1, T, H), dt),
             ((H,), jnp.float32), ((1, T, N), dt), ((1, T, N), dt))


@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_gmm_compiles_for_v5e(dtype, one_chip):
    dt = DTYPES[dtype]
    _compile(gmm, one_chip, ((512, 2048), dt), ((8, 2048, 1024), dt),
             ((8,), jnp.int32))


def test_uts_expand_compiles_for_v5e(one_chip):
    u32 = jnp.uint32
    _compile(uts_expand, one_chip, ((1024,), u32), ((1024,), u32),
             ((1024,), jnp.int32), ((MAX_CHILD,), u32))


# ------------------------------------------------ in-place pool updates
# The serving engine's jitted calls at Qwen2-1.5B widths, cut to 2
# layers: 8 slots, max_seq 2048 (64 blocks of 32), and a pool of 8 x
# slots x 64 blocks, float32 as the engine keeps it.
ENG_SLOTS, ENG_SEQ, ENG_BS = 8, 2048, 32
ENG_BLOCKS = 8 * ENG_SLOTS * (ENG_SEQ // ENG_BS)


def _engine_args(one_chip):
    """(cfg, params, pool, row, i32, key): shapes only, placed on the
    chip; ``row`` is the engine's prefill row cache. Weights are bf16 and
    norm scales float32, as the benchmark loads them; the kernels are
    forced on (the host's backend is the CPU)."""
    import dataclasses

    from repro.configs import ARCHS
    from repro.models import init_lm, make_cache, make_paged_cache

    cfg = dataclasses.replace(ARCHS["qwen2-1.5b"], n_layers=2,
                              tie_embeddings=True, attn_impl="pallas",
                              decode_impl="pallas")

    def on_chip(a, dtype=None):
        return jax.ShapeDtypeStruct(a.shape, dtype or a.dtype,
                                    sharding=one_chip)

    params = jax.tree_util.tree_map_with_path(
        lambda path, a: on_chip(a, jnp.float32 if "ln" in
                                jax.tree_util.keystr(path) else jnp.bfloat16),
        jax.eval_shape(lambda: init_lm(jax.random.key(0), cfg)))
    pool = jax.tree.map(on_chip, jax.eval_shape(
        lambda: make_paged_cache(cfg, ENG_BLOCKS, ENG_BS, ENG_SLOTS,
                                 dtype=jnp.float32)))
    row = jax.tree.map(on_chip, jax.eval_shape(
        lambda: make_cache(cfg, 1, ENG_SEQ, dtype=jnp.float32)))
    key = on_chip(jax.eval_shape(lambda: jax.random.key(0)))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    return cfg, params, pool, row, i32, key


def test_paged_decode_updates_the_pool_in_place(one_chip):
    """The engine's decode call (8 positions over both layers) makes no
    pool-sized buffer: its temporaries stay below one layer's K pool,
    and the donated k and v pools alias its outputs."""
    from repro.serve.engine import _make_decode_loop

    cfg, params, pool, _, i32, key = _engine_args(one_chip)
    decode = _make_decode_loop(cfg, ENG_SEQ, 8, 0.0)
    mb = ENG_SEQ // ENG_BS
    compiled = decode.lower(params, i32(ENG_SLOTS, 1), pool,
                            i32(ENG_SLOTS, mb), i32(ENG_SLOTS),
                            i32(ENG_SLOTS), key).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    pool_bytes = pool["k"].size * 4          # every layer's K pool
    assert mem.temp_size_in_bytes < pool_bytes // cfg.n_layers
    assert mem.alias_size_in_bytes >= 2 * pool_bytes


def test_paged_prefill_aliases_the_pool(one_chip):
    """The engine's admission (prefill into the row cache, scattered into
    the pool) aliases the donated k and v pools to its outputs."""
    from repro.serve.engine import _make_paged_fns

    cfg, params, pool, row, i32, key = _engine_args(one_chip)
    prefill, _ = _make_paged_fns(cfg, ENG_SEQ, ENG_BS, 0.0)
    compiled = prefill.lower(params, i32(1, 512), pool,
                             i32(ENG_SEQ // ENG_BS), i32(), row, i32(),
                             key).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * pool["k"].size * 4
