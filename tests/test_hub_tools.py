"""hub (hubconf protocol), program introspection (StableHLO text), op
benchmark harness, and style tooling."""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import paddle1_tpu as paddle
from paddle1_tpu.core.tensor import to_tensor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def hub_repo(tmp_path):
    (tmp_path / "hubconf.py").write_text(textwrap.dedent("""
        dependencies = ["numpy"]

        def tiny_mlp(hidden=8):
            \"\"\"A tiny MLP entrypoint.\"\"\"
            import paddle1_tpu as paddle
            return paddle.nn.Linear(4, hidden)

        def _private():
            pass
    """))
    return str(tmp_path)


class TestHub:
    def test_list(self, hub_repo):
        assert paddle.hub.list(hub_repo, source="local") == ["tiny_mlp"]

    def test_help(self, hub_repo):
        assert "tiny MLP" in paddle.hub.help(hub_repo, "tiny_mlp")

    def test_load(self, hub_repo):
        m = paddle.hub.load(hub_repo, "tiny_mlp", hidden=16)
        assert m.weight.shape == [4, 16]

    def test_unknown_entrypoint(self, hub_repo):
        from paddle1_tpu.core.errors import InvalidArgumentError
        with pytest.raises(InvalidArgumentError):
            paddle.hub.load(hub_repo, "nope")

    def test_remote_source_teaches(self, hub_repo):
        from paddle1_tpu.core.errors import PreconditionNotMetError
        with pytest.raises(PreconditionNotMetError, match="local"):
            paddle.hub.load("org/repo", "m", source="github")

    def test_missing_dependency(self, tmp_path):
        (tmp_path / "hubconf.py").write_text(
            "dependencies = ['not_a_real_pkg_xyz']\n"
            "def m():\n    return 1\n")
        from paddle1_tpu.core.errors import PreconditionNotMetError
        with pytest.raises(PreconditionNotMetError,
                           match="not_a_real_pkg_xyz"):
            paddle.hub.load(str(tmp_path), "m")


class TestProgramIntrospection:
    def test_to_static_program_text(self):
        @paddle.jit.to_static
        def f(x):
            return (x * 2.0 + 1.0).sum()

        txt = f.program_text(to_tensor(np.ones((4,), np.float32)))
        assert "stablehlo" in txt or "mhlo" in txt or "func" in txt
        assert "multiply" in txt  # the traced op is visible

    def test_translated_layer_program(self, tmp_path):
        from paddle1_tpu.jit import InputSpec, load, save
        lin = paddle.nn.Linear(4, 2)
        lin.eval()
        base = str(tmp_path / "m")
        save(lin, base, input_spec=[InputSpec([3, 4], "float32",
                                              name="x")])
        tl = load(base)
        txt = tl.program()
        assert "dot" in txt or "dot_general" in txt  # the matmul is there


class TestTools:
    @pytest.mark.slow  # ~14s subprocess; CI runs the op microbench
    # smoke as its own step, so in-tier duplication buys nothing
    def test_op_benchmark_single(self):
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "op_benchmark.py"),
             "--op", "add", "--shapes", "32x32,32x32", "--repeat", "2"],
            capture_output=True, text=True, timeout=300, cwd=REPO)
        assert r.returncode == 0, r.stderr
        rec = json.loads(r.stdout.strip().splitlines()[-1])
        assert rec["op"] == "add" and rec["jit_us_median"] > 0

    def test_suite_times_reads_a_junit_file_and_compares_two(self, tmp_path):
        """Six cases over two files, a class's method and two parametrised
        cases of one function among them: the wall, the sum, the table by
        file, the functions with their cases summed; and with a second file
        the differences."""
        from tools import suite_times

        def junit(wall, times):
            cases = "".join(
                f'<testcase classname="{c}" name="{n}" time="{t}" />'
                for (c, n), t in zip([
                    ("tests.test_a", "test_x[1]"), ("tests.test_a", "test_x[2]"),
                    ("tests.test_a", "test_y"),
                    ("tests.test_inGraph.TestK", "test_m"),
                    ("tests.sub.test_b", "test_z"),
                    ("tests.sub.test_b", "test_w")], times))
            path = tmp_path / f"{wall}.xml"
            path.write_text(
                '<?xml version="1.0"?><testsuites><testsuite name="pytest" '
                f'errors="0" failures="0" skipped="0" tests="6" '
                f'time="{wall}">{cases}</testsuite></testsuites>')
            return str(path)
        one = junit(10.0, [1.0, 2.0, 4.0, 8.0, 0.5, 0.25])
        wall, cases, files, funcs = suite_times.read(one)
        assert wall == 10.0 and sum(cases.values()) == 6
        assert files == {"tests/test_a.py": 7.0, "tests/test_inGraph.py": 8.0,
                         "tests/sub/test_b.py": 0.75}
        assert funcs["tests/test_a.py::test_x"] == 3.0
        text = suite_times.report(one)
        assert "wall 10.0 s, 6 cases, sum 15.8 s" in text
        by_file = text.split("file\n")[1].split("\n\n")[0].splitlines()
        assert [l.split()[-1] for l in by_file] == [
            "tests/test_inGraph.py", "tests/test_a.py", "tests/sub/test_b.py"]
        assert by_file[1].split()[:2] == ["7.0", "3"]
        two = junit(5.0, [0.5, 0.5, 4.0, 2.0, 0.5, 0.25])
        both = suite_times.report(one, two)
        assert "wall x0.500, sum x0.492" in both
        line, = [l for l in both.splitlines()
                 if l.endswith("tests/test_a.py::test_x")]
        assert line.split()[:3] == ["3.0", "1.0", "-2.0"]

    def test_check_style_passes(self):
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "check_style.py")],
            capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stdout
