"""Test bootstrap shared by every test module."""


def pytest_configure(config):
    # pytest-timeout is installed in CI (hard hang caps on the serve
    # jobs) but not in the base container; register the marker so local
    # runs don't warn about it
    config.addinivalue_line(
        "markers",
        "timeout(seconds): hard wall-clock cap, enforced when "
        "pytest-timeout is installed")
