"""Compile-cache placement (gaussiank_sgd_tpu/compile_cache.py): one knob,
JAX's own. Run in subprocesses — the placement is process-global jax config
and this process's was fixed by conftest.py."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CODE = r"""
import sys
sys.path.insert(0, %(repo)r)
import jax
updates = []
real_update = jax.config.update
def spy(name, value):
    updates.append(name)
    real_update(name, value)
jax.config.update = spy
from gaussiank_sgd_tpu.compile_cache import enable_compile_cache
returned = enable_compile_cache()
print(returned)
print(jax.config.jax_compilation_cache_dir)
print("jax_compilation_cache_dir" in updates)
"""


def _run(env_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _CODE % {"repo": REPO}],
                         env=env, capture_output=True, text=True,
                         timeout=120, check=True)
    return out.stdout.strip().splitlines()[-3:]


def test_env_set_means_no_directory_is_set_in_code(tmp_path):
    returned, effective, set_in_code = _run(str(tmp_path))
    assert returned == effective == str(tmp_path)
    assert set_in_code == "False"


def test_env_unset_means_the_fixed_in_checkout_directory():
    returned, effective, set_in_code = _run(None)
    assert returned == effective == os.path.join(REPO, ".jax_cache")
    assert set_in_code == "True"
    # ... which git ignores
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
