"""The entry points' compile-cache rule (repro.launch.compile_cache): the
cache lands in $JAX_COMPILATION_CACHE_DIR when it is set, else at one fixed
in-checkout path; importing repro never turns it on. Each case runs in a
fresh interpreter, since JAX's cache configuration is process-global."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

SCRIPT = r"""
import json, os
import jax, jax.numpy as jnp
import repro, repro.models, repro.serve.engine
before = jax.config.jax_compilation_cache_dir
from repro.launch.compile_cache import enable_compile_cache
path = enable_compile_cache()
if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: jnp.sin(x) * 2)(jnp.arange(8.0)).block_until_ready()
print("RESULT" + json.dumps({"before": before, "path": path,
                             "config": jax.config.jax_compilation_cache_dir}))
"""


def _run(env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")]
    return json.loads(line[-1][len("RESULT"):])


@pytest.mark.parametrize("env_set", [False, True], ids=["fixed", "env"])
def test_compile_cache_location(env_set, tmp_path):
    out = _run(tmp_path if env_set else None)
    if env_set:
        # JAX reads the variable itself; the helper sets no other dir,
        # and compiled programs land there.
        assert out["path"] == out["config"] == str(tmp_path)
        assert any(tmp_path.iterdir()), "nothing was cached"
    else:
        assert out["before"] is None, "importing repro enabled the cache"
        assert out["path"] == out["config"] == str(ROOT / ".jax_cache")
