"""The one rule for where the persistent XLA compile cache lives
(utils/compile_cache.py): `JAX_COMPILATION_CACHE_DIR` when it is set —
and then the code names no directory of its own — else one fixed,
git-ignored directory inside the checkout; in both cases the code sets
the keep-every-compile threshold and nothing else. Asked of fresh
processes, because the answer is settled when JAX is imported: one
started directly, and one started with the environment
`rpc.cluster.launch` builds for a service host and for the store
server, which must land on the directory their parent says.
"""
import json
import os
import subprocess
import sys

import pytest

from cadence_tpu.rpc import cluster
from cadence_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: records everything the code itself hands to jax.config, then reports
#: it next to what enable() returned and what JAX ended up with
_PROBE = """
import json, jax
set_in_code = {}
real_update = jax.config.update
def spy(name, value):
    set_in_code[name] = value
    return real_update(name, value)
jax.config.update = spy
from cadence_tpu.utils import compile_cache
used = compile_cache.enable()
print(json.dumps({"used": used, "set_in_code": set_in_code,
                  "jax": jax.config.jax_compilation_cache_dir}))
"""


def _direct_env():
    return dict(os.environ, PYTHONPATH=REPO)


def _host_env():
    return cluster.child_env("host-0", "host")


def _store_env():
    return cluster.child_env("store", "store")


@pytest.mark.parametrize("make_env", [_direct_env, _host_env, _store_env],
                         ids=["direct", "launch-host", "launch-store"])
@pytest.mark.parametrize("env_set", [True, False], ids=["set", "unset"])
def test_cache_directory_rule(make_env, env_set, tmp_path, monkeypatch):
    outside = str(tmp_path / "placed-from-outside")
    if env_set:
        monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, outside)
    else:
        monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
    env = make_env()
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", _PROBE], env=env,
                         capture_output=True, text=True, timeout=120,
                         cwd=str(tmp_path))
    assert out.returncode == 0, out.stderr[-2000:]
    doc = json.loads(out.stdout.strip().splitlines()[-1])
    # what is kept is the same wherever the cache lives: every compile
    keep_all = {"jax_persistent_cache_min_compile_time_secs": 0.0}
    if env_set:
        assert doc == {"used": outside, "set_in_code": keep_all,
                       "jax": outside}
    else:
        fixed = os.path.join(REPO, ".jax_cache")
        assert doc == {"used": fixed, "jax": fixed, "set_in_code": dict(
            keep_all, jax_compilation_cache_dir=fixed)}
        assert os.path.isdir(fixed)


def test_fixed_directory_is_inside_the_checkout_and_ignored():
    assert compile_cache.DEFAULT_DIR == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_cache_dir_needs_no_jax(monkeypatch):
    """The launcher asks where the cache is without importing JAX."""
    monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, "/somewhere/else")
    assert compile_cache.cache_dir() == "/somewhere/else"
    monkeypatch.delenv(compile_cache.CACHE_DIR_ENV)
    assert compile_cache.cache_dir() == compile_cache.DEFAULT_DIR
