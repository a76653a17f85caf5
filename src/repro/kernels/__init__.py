"""Pallas TPU kernels for the perf-critical compute layers, each with a
pure-jnp oracle in ref.py and a dispatching wrapper in ops.py:

  uts_expand.py      — the paper's UTS hot loop: batched node hashing +
                       geometric child counts (VPU integer mixing)
  flash_attention.py — causal GQA flash attention (online softmax, VMEM
                       scratch across the sequential kv grid dim, causal
                       block skip)
  flash_decode.py    — split-KV Sq==1 decode against a padded KV cache
                       (per-slot length masking, idle-slot/tail block
                       skip; the serving hot path)
  paged_decode.py    — decode and chunked prefill against a paged,
                       block-table-indirected KV pool
  mamba2_ssd.py      — Mamba2 SSD chunk scan (matmul-form intra-chunk +
                       carried (N,P) state)
  moe_gmm.py         — grouped matmul for sort-dispatched MoE experts

Every block is laid out so its last two dims tile as (8, 128) or cover
whole array dims (heads ahead of tokens), which the TPU compiler requires;
tests/test_tpu_compile.py compiles each kernel for a described v5e chip.
The models call ops.*, which selects the kernel on TPU and the oracle
elsewhere; on CPU the tests run the kernels with interpret=True.
"""
from . import ops, ref  # noqa: F401
