"""Test configuration.

The CPU-backend forcing (8 virtual devices, JAX_PLATFORMS=cpu) lives
in the repo-root ``conftest.py`` so the doctest gate shares it; pytest
loads that conftest before this one for everything under tests/, so
this file only registers markers.
"""


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-minute statistical tests (deselect with "
        "-m 'not slow'; they still run by default)",
    )
