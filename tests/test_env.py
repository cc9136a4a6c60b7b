"""`repro.env.use_compile_cache`: JAX's persistent compilation cache goes
where JAX_COMPILATION_CACHE_DIR says, or else to the checkout's fixed
`.jax_cache`.  Each case runs in a fresh interpreter, since the setting is
process-wide."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]

SCRIPT = textwrap.dedent("""
    import jax, jax.numpy as jnp
    from repro.env import use_compile_cache
    print("DIR", use_compile_cache())
    print("CONFIG", jax.config.jax_compilation_cache_dir)
    if {compile!r}:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()
""")


def _run(env_dir, compile_something):
    env = dict(os.environ, PYTHONPATH=f"{REPO}/src", JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(compile=compile_something)],
        env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    lines = dict(line.split(" ", 1) for line in r.stdout.splitlines())
    return lines["DIR"], lines["CONFIG"]


def test_compile_cache_goes_where_the_environment_says(tmp_path):
    cache = tmp_path / "cache"
    got, config = _run(cache, True)
    assert got == config == str(cache)
    assert any(cache.iterdir()), "no compiled program was written"


def test_compile_cache_defaults_to_the_checkout():
    got, config = _run(None, False)
    assert got == config == str(REPO / ".jax_cache")
