"""Shared fixtures.  Tests run on the single CPU device; the multi-device
tests force extra host devices in a subprocess, never here."""
import jax
import pytest

# the lint-fixture tree holds deliberate violations (including a direct
# `import hypothesis`); it is linted via --root by test_analysis.py, never
# collected as tests
collect_ignore = ["fixtures"]


@pytest.fixture
def key():
    return jax.random.PRNGKey(0)


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running integration test")
    config.addinivalue_line(
        "markers",
        "multidevice: forced-8-device subprocess test (see ROADMAP)")
