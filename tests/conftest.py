import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: subprocess / multi-device integration tests")
    config.addinivalue_line(
        "markers",
        "pallas_interpret: Pallas kernel parity tests that run in "
        "interpret mode on the CPU backend")


@pytest.fixture(scope="session")
def pallas_interpret():
    """Force interpret mode for Pallas kernels under test.

    Returns True (the value to pass as ``interpret=``): XLA's CPU
    backend runs ``pallas_call`` only interpreted. Session-scoped
    because it is constant, which also lets hypothesis ``@given`` tests
    take it (hypothesis refuses function-scoped fixtures).
    """
    return True
