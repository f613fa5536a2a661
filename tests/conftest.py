"""Test fixtures (reference parity: the reference's ``conftest.py:61-156``
seeds RNGs from MXNET_MODULE_SEED/MXNET_TEST_SEED with repro logging and
waitall-fences between modules).

The suite runs on a virtual 8-device CPU mesh so every sharding/collective
path is exercised without TPU hardware (SURVEY.md §4: the multi-process-on-
one-host trick, TPU edition)."""
import logging
import os

# The CPU backend with 8 virtual devices, set BEFORE any backend init.
# JAX_PLATFORMS=cpu in the environment is enough to get it; the
# config.update below makes a bare ``pytest`` run the same.
prev = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in prev:
    os.environ["XLA_FLAGS"] = \
        prev + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

if os.environ.get("MXNET_TEST_DEVICE", "cpu") == "cpu":
    jax.config.update("jax_platforms", "cpu")

import numpy as _onp  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from tier-1 "
        "(-m 'not slow')")
    config.addinivalue_line(
        "markers", "chaos: randomized-but-seeded fault-injection runs "
        "(tools/chaos_check.py); implies slow, so excluded from tier-1")
    config.addinivalue_line(
        "markers", "dist: multi-process jax.distributed tests (spawned "
        "via tools/launch.py); implies slow, so excluded from tier-1 — "
        "run explicitly with `-m dist`")
    config.addinivalue_line(
        "markers", "integration: cross-component tests driving real "
        "subprocesses/services")


def pytest_collection_modifyitems(config, items):
    # chaos tests are long, randomized (seeded) end-to-end loops — keep
    # them out of the `-m 'not slow'` tier-1 set automatically; same for
    # dist tests (multi-process jobs), which also auto-acquire the
    # marker by living in test_dist.py
    for item in items:
        if os.path.basename(str(item.fspath)) == "test_dist.py":
            item.add_marker(pytest.mark.dist)
        if "chaos" in item.keywords or "dist" in item.keywords:
            item.add_marker(pytest.mark.slow)


@pytest.fixture(autouse=True)
def seed_and_fence(request):
    """Seed python/numpy/mx RNGs per test with logged repro (reference
    conftest function_scope_seed) and waitall-fence afterwards so async
    failures attribute to the right test."""
    import random

    import mxnet_tpu as mx
    seed = os.environ.get("MXNET_TEST_SEED")
    if seed is None:
        # mxlint: disable=R6 -- this unseeded draw IS the seed source
        # (randomized testing by design); the repro path is the
        # MXNET_TEST_SEED value logged on failure below
        seed = _onp.random.randint(0, 2 ** 31)
    else:
        seed = int(seed)
    random.seed(seed)  # image augs draw from python random (R6: the
    # docstring always promised python/numpy/mx; now all three are true)
    _onp.random.seed(seed)
    mx.np.random.seed(seed)
    yield
    if request.node.rep_call.failed if hasattr(request.node, "rep_call") \
            else False:
        logging.warning("To reproduce: MXNET_TEST_SEED=%d pytest %s",
                        seed, request.node.nodeid)
    mx.waitall()


@pytest.hookimpl(tryfirst=True, hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    rep = outcome.get_result()
    setattr(item, "rep_" + rep.when, rep)


@pytest.fixture()
def interpret_kernels(monkeypatch):
    """The Pallas kernels' own code in the interpreter, on the CPU
    (``MXNET_PALLAS_INTERPRET``'s switch) instead of the dense stand-in."""
    from mxnet_tpu.ops import pallas_ops
    monkeypatch.setattr(pallas_ops, "_INTERPRET", True)
