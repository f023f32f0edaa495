"""enable_compile_cache(): where the persistent compile cache lands.

Each case runs in a fresh interpreter, because the cache is set up at
the process's first compile. The child points the helper's default at
a directory of the case's own, so no case writes into the checkout's
``.jax_cache/`` or sees another case's writes."""

import os
import subprocess
import sys
import textwrap

from repro.compile_cache import DEFAULT_CACHE_DIR

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROGRAM = textwrap.dedent("""
    import sys
    import jax, jax.numpy as jnp
    from repro import compile_cache
    compile_cache.DEFAULT_CACHE_DIR = sys.argv[1]
    print(compile_cache.enable_compile_cache())
    jax.block_until_ready(jax.jit(lambda x: jnp.sin(x) * 3.0)(jnp.ones(7)))
""")


def _run(default_dir, env_dir):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", PROGRAM, default_dir],
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()[-1]


def _listing(path):
    return sorted(os.listdir(path)) if os.path.isdir(path) else []


def test_default_cache_dir_is_fixed_in_checkout():
    assert DEFAULT_CACHE_DIR == os.path.join(REPO, ".jax_cache")


def test_cache_defaults_to_fixed_dir_in_checkout(tmp_path):
    default = str(tmp_path / "default")
    assert _run(default, None) == default
    assert _listing(default), "nothing was written to the default directory"


def test_cache_env_var_stays_in_charge(tmp_path):
    default, env_dir = str(tmp_path / "default"), str(tmp_path / "env")
    assert _run(default, env_dir) == env_dir
    assert _listing(env_dir), "nothing was written to JAX_COMPILATION_CACHE_DIR"
    assert _listing(default) == []
