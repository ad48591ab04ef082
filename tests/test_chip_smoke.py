"""chip_smoke.py and the compile-cache placement, as far as a host without
a chip can check them: the smoke must FAIL here, never run on the CPU."""

import json
import os
import subprocess
import sys

from horovod_tpu.common import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_fails_without_a_chip():
    """No chip means failure: a non-zero exit and ``"ok": false`` on the
    last line, after the first phase and nothing else."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                          env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0, proc.stdout
    lines = [json.loads(line) for line in proc.stdout.splitlines()]
    assert lines[-1]["ok"] is False, proc.stdout
    assert lines[-1]["failed"] == ["device"], proc.stdout
    assert [line["phase"] for line in lines[:-1]] == ["device"], proc.stdout
    assert "no TPU" in proc.stderr


def test_chip_smoke_parent_stays_off_jax():
    """The parent holds no chip: importing the script and building its
    argument parser must not import JAX (its children do)."""
    code = ("import sys; sys.argv = ['chip_smoke.py', '--help']\n"
            "import chip_smoke\n"
            "try:\n    chip_smoke.main()\nexcept SystemExit:\n    pass\n"
            "assert 'jax' not in sys.modules, 'the parent imported jax'\n")
    subprocess.run([sys.executable, "-c", code], cwd=REPO, check=True,
                   capture_output=True, timeout=60)


def test_compile_cache_follows_env_or_fixed_in_tree_path():
    """Where JAX_COMPILATION_CACHE_DIR is set it is all there is; where it
    is not, one fixed directory inside the checkout — the same on every
    call, so the cache (whose path is part of its key) can hit."""
    env = {"JAX_COMPILATION_CACHE_DIR": "/somewhere/else"}
    assert compile_cache.place_compile_cache(env) == "/somewhere/else"
    assert env == {"JAX_COMPILATION_CACHE_DIR": "/somewhere/else"}
    env = {}
    in_tree = compile_cache.place_compile_cache(env)
    assert in_tree == os.path.join(REPO, ".jax_compilation_cache")
    assert env == {"JAX_COMPILATION_CACHE_DIR": in_tree}
    assert compile_cache.place_compile_cache({}) == in_tree
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_compilation_cache/" in f.read().split()


def test_launcher_ranks_get_the_cache_directory():
    from horovod_tpu.runner import make_rank_env

    env = make_rank_env(0, 1, "127.0.0.1:1", ["127.0.0.1:2"], base_env={})
    assert env["JAX_COMPILATION_CACHE_DIR"].endswith(".jax_compilation_cache")
    env = make_rank_env(0, 1, "127.0.0.1:1", ["127.0.0.1:2"],
                        base_env={"JAX_COMPILATION_CACHE_DIR": "/x"})
    assert env["JAX_COMPILATION_CACHE_DIR"] == "/x"
