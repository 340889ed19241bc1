"""What a rehearsal may not leave behind in its worker's process, and
the benchmarks the checks of the lists are held against.

``test_rehearsal.py``'s ``harness`` puts ``aotcache._state`` back as
it found it.  Where the run under test was the process's first to
install the program's listeners on JAX's monitoring bus, that put
``listeners_installed`` back to False with the listeners still on the
bus, and the next test to turn the compile cache on (here or in
``tests/unit/test_tracing_spans.py``, whichever file the worker took
next) installed them a second time: every compile, cache load and
back-dated span of the rest of the process counted twice.  So the
flag is made true, and the listeners are on the bus exactly once,
before ``harness`` saves the state.
"""

import os

import pytest
import rehearsal_benchmarks


@pytest.fixture(autouse=True)
def the_programs_listeners_are_on_the_bus_once():
    from jax._src import monitoring

    from pydcop_tpu.engine import aotcache

    on_the_bus = aotcache._on_event in monitoring.get_event_listeners()
    with aotcache._lock:
        aotcache._state["listeners_installed"] = on_the_bus
    aotcache.install_listeners()
    yield
    assert monitoring.get_event_listeners().count(aotcache._on_event) == 1


@pytest.fixture(scope="session")
def benchmarks(tmp_path_factory):
    """``{which: (path of its BENCHMARK.json, its data directory)}``:
    the repository's own benchmark, and the copy with a fourth cell
    (``rehearsal_benchmarks.py``), which no test writes to."""
    return {
        "real": (os.path.join(rehearsal_benchmarks.REPO, "BENCHMARK.json"),
                 rehearsal_benchmarks.CHIPBENCH),
        "fourth_cell": rehearsal_benchmarks.build(
            str(tmp_path_factory.mktemp("fourth_cell")))}
