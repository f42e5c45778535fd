"""The persistent compilation cache lands where ``enable_compile_cache``
says: ``$JAX_COMPILATION_CACHE_DIR`` when set, else the one fixed
directory inside the checkout. Each case runs in a child so the cache
settings never leak into the test process."""
import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = textwrap.dedent("""
    import jax, jax.numpy as jnp
    from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache
    got = enable_compile_cache()
    print("DIR", got)
    print("CONFIG", jax.config.jax_compilation_cache_dir)
    print("REPO", REPO_CACHE_DIR)
    if jax.config.jax_compilation_cache_dir != str(REPO_CACHE_DIR):
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        jax.block_until_ready(jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)))
""")


def _run(env_dir=None):
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-3000:]
    return dict(line.split(" ", 1) for line in r.stdout.splitlines())


def test_compile_cache_uses_env_dir(tmp_path):
    d = str(tmp_path / "jcache")
    out = _run(d)
    assert out["DIR"] == d and out["CONFIG"] == d
    assert os.listdir(d), "no entry written to $JAX_COMPILATION_CACHE_DIR"


def test_compile_cache_defaults_to_fixed_repo_dir():
    out = _run()
    assert out["DIR"] == out["CONFIG"] == out["REPO"]
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    assert out["REPO"] == os.path.join(root, ".jax_cache")
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
