"""Test config: run on a virtual 8-device CPU mesh (the standard JAX trick
— SURVEY.md §4 fixture 5) so multi-chip sharding logic is exercised without
TPU hardware.  Must set env before jax initialises."""

import os

# Tests are CPU-mesh by design, on a chip host too: a test session
# never takes the chip.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: heavier tests excluded from the tier-1 "
        "'not slow' budget run")


@pytest.fixture(autouse=True)
def _arm_page_sanitizer(request):
    """Arm the serving-lifecycle page sanitizer for every test in the
    serving/speculative suites (ISSUE 17 acceptance: the parity suites
    run sanitizer-armed).  The sanitizer is pure host bookkeeping — zero
    extra compiled programs, streams bit-identical — and pages allocated
    before arming are exempt, so module-scoped engines stay legal."""
    mod = getattr(request.module, "__name__", "")
    if not ("serving" in mod or "speculative" in mod):
        yield
        return
    from mxtpu.analysis.lifecycle_check import page_sanitizing
    with page_sanitizing():
        yield


@pytest.hookimpl(tryfirst=True)
def pytest_runtest_setup(item):
    """Every test — and every fixture first built for it, module-scoped
    ones included: this runs before them — starts from the same mxtpu
    key stream and numpy state, whatever ran before it in its worker
    (parity: tests/python/unittest/common.py with_seed()).  The global
    key ring is seeded at random when mxtpu is imported and is never
    reset, so a test that initializes parameters without seeding drew
    weights that depended on the process and on every draw before it:
    test_quantize_net_gluon_roundtrip met two logits 3.9e-5 apart on
    about one stream in twenty and failed in the driver's run only."""
    import mxtpu as mx

    np.random.seed(0)
    mx.random.seed(0)


def assert_almost_equal(a, b, rtol=1e-5, atol=1e-6):
    import mxtpu as mx

    if isinstance(a, mx.NDArray):
        a = a.asnumpy()
    if isinstance(b, mx.NDArray):
        b = b.asnumpy()
    np.testing.assert_allclose(a, b, rtol=rtol, atol=atol)
