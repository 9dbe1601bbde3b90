"""Where the persistent compile cache lives (core/compile_cache.py):
JAX's own ``JAX_COMPILATION_CACHE_DIR`` when set — and then this module
sets no directory at all — else one fixed path inside the checkout,
the same from any working directory. Each case runs in a fresh
interpreter: ``jax.config`` is process-global and conftest has already
placed this process's cache."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_PROBE = (
    "import json, jax\n"
    "from mmlspark_tpu.core.compile_cache import enable_persistent_cache\n"
    "before = jax.config.jax_compilation_cache_dir\n"
    "print(json.dumps([before, enable_persistent_cache(),\n"
    "                  jax.config.jax_compilation_cache_dir]))\n")


def _probe(cwd, cache_env):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_env
    r = subprocess.run([sys.executable, "-c", _PROBE], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_env_var_is_left_to_jax(tmp_path):
    placed = str(tmp_path / "placed-from-outside")
    before, returned, after = _probe(str(tmp_path), placed)
    # JAX read the variable itself; the function reports it and sets
    # nothing (and creates nothing: JAX makes the directory on first
    # write)
    assert before == returned == after == placed
    assert not os.path.exists(placed)


def test_default_is_one_path_inside_the_checkout(tmp_path):
    here = _probe(REPO, None)
    elsewhere = _probe(str(tmp_path), None)
    assert here == elsewhere
    before, returned, after = here
    assert before is None
    assert returned == after == os.path.join(REPO, ".jax_cache")
    # commit nothing under the cache path: the driver copies the tree
    # (read the file: a checkout need not be a git repository)
    with open(os.path.join(REPO, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split()
