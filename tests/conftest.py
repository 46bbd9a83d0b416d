"""Shared test configuration: registers the ``slow`` marker."""


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running multi-device test")
