"""Shared pytest config.  NOTE: no XLA_FLAGS here — smoke tests and benches
must see the 1 real CPU device; multi-device tests use subprocesses.
"""


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: multi-device subprocess tests")
