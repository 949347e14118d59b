"""utils/platform.py: where the persistent compile cache goes, and that
asking does not wake a backend (tools call ``cli_bootstrap`` BEFORE
``jax.distributed.initialize`` / ``force_cpu``; on a machine with a chip a
backend touched there is the chip taken)."""

import json
import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = """
import json, jax
from jax._src import xla_bridge
from mx_rcnn_tpu.utils.platform import cli_bootstrap
cli_bootstrap()
print(json.dumps({
    "dir": jax.config.jax_compilation_cache_dir,
    "backend_up": xla_bridge.backends_are_initialized(),
}))
"""


def _bootstrap_in_child(cache_env):
    """Run ``cli_bootstrap()`` in a fresh interpreter → what it set."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_env
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD], env=env, capture_output=True,
        text=True, timeout=120, check=True,
    )
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("given", ["/some/where/else", "relative/dir//x/"])
def test_cache_dir_from_environment_is_used_verbatim(given):
    got = _bootstrap_in_child(given)
    assert got["dir"] == given  # byte for byte: no subdirectory, no norm
    assert not got["backend_up"]


def test_cache_dir_unset_is_fixed_inside_checkout():
    first = _bootstrap_in_child(None)
    second = _bootstrap_in_child(None)
    assert first["dir"] == second["dir"]  # the path is part of the key
    assert first["dir"].startswith(os.path.join(REPO_ROOT, ".jax_cache") + os.sep)
    assert not first["backend_up"]
