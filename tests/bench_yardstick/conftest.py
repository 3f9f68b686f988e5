"""The yardstick tests' one marker.

``drives_a_run`` marks a test that builds the program and steps it, in
this process or in a child (tens of seconds each).
``test_the_door_stays_open.py`` runs every other test of this directory
once more on a copy of the benchmark that holds appended entries, and
leaves the marked ones out there: what they check does not depend on where
an entry stands in ``BENCHMARK.json``.
"""


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "drives_a_run: builds the program and steps it; left out "
        "where the yardstick tests run on a copy with appended entries")
