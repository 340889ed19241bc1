"""Test configuration.

The CPU-backend forcing (8 virtual devices, JAX_PLATFORMS=cpu) lives
in the repo-root ``conftest.py`` so the doctest gate shares it; pytest
loads that conftest before this one for everything under tests/, so
this file only registers markers and keeps one test's process-level
state from the next.
"""

import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-minute statistical tests (deselect with "
        "-m 'not slow'; they still run by default)",
    )


@pytest.fixture(autouse=True)
def as_in_a_new_process():
    """Process-level state of the program that one test would hand
    the next.

    The solo MaxSum engine's programs and their warmth belong to the
    process (engine/runner.py): what one test traced, the next would
    dispatch.  Every test starts as a new process does, so a test
    that plants a fault in an ops function gets it traced, and
    "first" means first in the test.

    Several fixtures put ``aotcache._state`` back whole when a test
    ends, ``listeners_installed`` with it, while the listeners stay on
    JAX's monitoring bus; the next ``install_listeners()`` then put
    them there a second time and every compile counted double.  This
    fixture is torn down after those: the flag says what is on the
    bus."""
    from jax._src import monitoring

    from pydcop_tpu.engine import aotcache
    from pydcop_tpu.engine.runner import reset_process_programs

    reset_process_programs()
    yield
    with aotcache._lock:
        aotcache._state["listeners_installed"] = (
            aotcache._on_event in monitoring.get_event_listeners())
