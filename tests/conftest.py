"""Test harness: run everything on CPU with 8 virtual devices.

This is the TPU-world "fake backend" the reference never had (SURVEY §5.1):
multi-chip sharding paths compile and execute on 8 XLA host devices, so DP
correctness is tested without hardware.  The platform is pinned through
jax.config so the suite runs on the CPU whether or not ``JAX_PLATFORMS``
is exported (and whether or not a chip is attached).
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

import jax

jax.config.update("jax_platforms", "cpu")

# persistent compile cache: recompiles across test runs are the dominant
# cost of the suite.  Same placement rule as every tool (after XLA_FLAGS
# is final — the in-checkout default is keyed on it)
from mx_rcnn_tpu.utils.platform import enable_compile_cache

enable_compile_cache()

assert jax.devices()[0].platform == "cpu", "tests must run on host CPU"
assert len(jax.devices()) == 8, "expected 8 virtual CPU devices"

import numpy as np
import pytest

# ---------------------------------------------------------------------------
# Per-test wall-clock deadline (VERDICT r4 weak #6): a hang must fail
# loudly, not be indistinguishable from a slow compile.  pytest-timeout is
# not in this image, so a WATCHDOG THREAD (pytest-timeout's "thread"
# method): a SIGALRM guard can't fire while the main thread is wedged
# inside native XLA code (the signal is only delivered at a bytecode
# boundary), and it wouldn't cover fixture setup — where the big
# model-init compiles live.  The watchdog wraps the WHOLE runtest
# protocol (setup+call+teardown), dumps every thread's stack on expiry,
# and os._exit(70)s: the run dies loudly at the offending test instead
# of stalling forever.  Deadlines: generous default for cold
# compiles; long tests carry ``@pytest.mark.deadline(n)`` (0 disables);
# override globally with MX_RCNN_TEST_TIMEOUT.
# ---------------------------------------------------------------------------
_DEADLINE = int(os.environ.get("MX_RCNN_TEST_TIMEOUT", "900"))


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy compile-bound test, excluded from `make test-fast`",
    )
    config.addinivalue_line(
        "markers",
        "deadline(secs): per-test wall-clock deadline override (0 = none)",
    )


@pytest.hookimpl(wrapper=True)
def pytest_runtest_protocol(item, nextitem):
    import faulthandler
    import sys
    import threading

    marker = item.get_closest_marker("deadline")
    secs = int(marker.args[0]) if marker else _DEADLINE
    if secs <= 0:
        return (yield)

    def _expired():
        # suspend pytest's capture first (pytest-timeout does the same):
        # with fd-level capture the dump would land in a capture temp
        # file that os._exit discards, leaving exit code 70 and zero
        # diagnostics — the exact silent-hang failure this guard fixes
        try:
            capman = item.config.pluginmanager.getplugin("capturemanager")
            if capman is not None:
                capman.suspend_global_capture(in_=True)
        except Exception:
            pass
        sys.stderr.write(
            f"\n=== DEADLINE: {item.nodeid} exceeded {secs}s — dumping "
            f"all thread stacks and aborting the run (raise with "
            f"@pytest.mark.deadline(n) or MX_RCNN_TEST_TIMEOUT) ===\n"
        )
        faulthandler.dump_traceback(file=sys.stderr)
        sys.stderr.flush()
        os._exit(70)

    watchdog = threading.Timer(secs, _expired)
    watchdog.daemon = True
    watchdog.start()
    try:
        return (yield)
    finally:
        watchdog.cancel()


@pytest.fixture
def rng():
    return np.random.RandomState(0)
