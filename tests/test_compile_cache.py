"""Persistent compile cache placement (``repro.launch.compile_cache``).

Each case runs in a fresh subprocess: the cache directory is process-global
JAX config, and this test process must keep its own."""
import json
import os
import subprocess
import sys
import textwrap

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = textwrap.dedent(
    """
    import json, os
    import jax, jax.numpy as jnp
    from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache

    def listing(d):
        return sorted(os.listdir(d)) if os.path.isdir(d) else []

    repo_before = listing(REPO_CACHE_DIR)
    d = enable_compile_cache()
    out = {"dir": d, "cfg": jax.config.jax_compilation_cache_dir,
           "repo_cache": str(REPO_CACHE_DIR)}
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(5.0)).block_until_ready()
        out["entries"] = listing(d)
        out["repo_unchanged"] = listing(REPO_CACHE_DIR) == repo_before
    print(json.dumps(out))
    """
)


def _run(env):
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT], capture_output=True, text=True,
        env=env, cwd=_REPO, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH="src", JAX_PLATFORMS="cpu", **extra)
    return env


def test_env_dir_receives_compiles_and_nothing_else(tmp_path):
    d = str(tmp_path / "cc")
    res = _run(_env(JAX_COMPILATION_CACHE_DIR=d))
    assert res["dir"] == d and res["cfg"] == d
    assert res["entries"], "no compiled program written to JAX_COMPILATION_CACHE_DIR"
    assert res["repo_unchanged"]


def test_default_dir_is_fixed_in_repo():
    res = _run(_env())
    want = os.path.join(_REPO, ".jax_cache")
    assert res["dir"] == res["cfg"] == res["repo_cache"] == want
