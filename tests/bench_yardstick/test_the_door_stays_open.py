"""A later PR may add to the benchmark and may edit no file of it: so the
yardstick's own tests have to pass on a tree to which a configuration, a
cell and a per-layer metric were appended. The driver reads
``BENCHMARK.json``'s ``configs``, ``workloads`` and ``per_layer`` by place
(an entry put before the end is a change to the entry whose place it
took), so the ends of the lists are the one place a later PR can write to.

The rule this holds every other test of the directory to: a test may say
that a cell is in a list, that a cell's metrics include a set, that a
metric's file and entry agree and what a reducer reads from a synthetic
view; never which entry stands last, that no other cell lists a metric,
that every cell has one chip, or which files name a reducer."""

import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import probe_copy  # noqa: E402


@pytest.mark.drives_a_run
@pytest.mark.parametrize("chips", [1, 4])
def test_appended_entries_fail_no_yardstick_test(tmp_path, chips):
    """Every yardstick test that does not itself build and step the
    program, run on the copy: the probe cell on one chip, and on four (the
    one four-chip cell a benchmark of eight may have)."""
    probe_copy.make(tmp_path, chips=chips, with_tests=True)
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT}
    done = subprocess.run(
        [sys.executable, "-m", "pytest", "tests/bench_yardstick", "-v",
         "-m", "not drives_a_run", "-p", "no:cacheprovider",
         "-p", "no:randomly", "-x"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-1000:]
    # the copy's tests ran, on the copy's benchmark, and read the probe
    for case in (f"test_config_file[{probe_copy.CONFIG}]",
                 f"test_workload_file[{probe_copy.CELL}]",
                 f"test_layer_metric_file[{probe_copy.METRIC}]"):
        assert case + " PASSED" in done.stdout, case
