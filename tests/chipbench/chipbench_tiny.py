"""Tiny stand-ins for the benchmark's configurations and mixes, for CPU
rehearsals in the tests: the same keys at toy sizes."""
import copy
import json
import os

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
BENCH = os.path.join(ROOT, "benchmarks", "chip")


def load(kind: str, name: str) -> dict:
    with open(os.path.join(BENCH, kind, f"{name}.json")) as f:
        return json.load(f)


def dense(dtype: str = "bfloat16") -> dict:
    hf = load("configs", "qwen2-1.5b")
    hf.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
              num_attention_heads=4, num_key_value_heads=2, head_dim=16,
              vocab_size=500, torch_dtype=dtype)
    hf["engine"] = dict(max_slots=4, max_seq=256, steps_per_sync=4,
                        kv_pool_tokens=1024)
    return hf


def serve_mix(name: str) -> dict:
    mix = copy.deepcopy(load("traffic", name))
    mix.update(rate_per_s=8.0, check_requests=3,
               drain_limit_s=30)
    mix["prompt_tokens"].update(median=32, min=8, max=100)
    mix["output_tokens"].update(median=8, min=2, max=16)
    return mix


def published_widths(layers: int = 4, vocab: int = 8192) -> dict:
    """The serving configuration at its published widths, with fewer
    layers, a smaller vocabulary and a small engine."""
    hf = load("configs", "qwen2-1.5b")
    hf.update(num_hidden_layers=layers, vocab_size=vocab)
    hf["engine"] = dict(max_slots=4, max_seq=256, steps_per_sync=4,
                        kv_pool_tokens=1024)
    return hf


def run_serve(cell: str, seconds: float = 1.5, seed: int = 2 ** 33 + 11,
              config=None, mix=None, control: bool = False, log=None):
    """One CPU rehearsal of a serving cell, at the tiny sizes unless a
    configuration or mix is given."""
    import time

    from benchmarks.chip import harness

    if mix is None:
        mix = serve_mix(harness.find_cell(harness.load_benchmark(),
                                          cell)["traffic"])
    return harness.run_cell(cell, seed, seconds, False,
                            t_start=time.perf_counter(), rehearsal=True,
                            config_override=config or dense(),
                            mix_override=mix, control=control,
                            log=log or (lambda m: None))
