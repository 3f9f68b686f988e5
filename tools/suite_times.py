"""Where the test suite's time goes, from a junit XML (pytest --junitxml).

    python tools/suite_times.py [junit.xml] [other.xml]

One file: the wall, the sum of case times, the table by test file and the 30
longest functions (parametrised cases summed).  Two files: the same tables
with the second's numbers and the difference beside the first's.  Case times
are read under whatever load the run had; compare runs of one command on one
machine.  Standard library only.
"""
import collections
import sys
import xml.etree.ElementTree as ET


def read(path):
    """(wall seconds, {file: cases}, {file: seconds}, {file::function: seconds})."""
    suite = ET.parse(path).getroot().find("testsuite")
    cases = collections.Counter()
    files = collections.defaultdict(float)
    funcs = collections.defaultdict(float)
    for case in suite.iter("testcase"):
        # classname is the module's dotted path (its last part test_*), with
        # a test class's name (Test*) after it where the case is a method
        parts = case.get("classname").split(".")
        while len(parts) > 1 and not parts[-1].startswith("test_"):
            parts.pop()
        file = "/".join(parts) + ".py"
        t = float(case.get("time", 0))
        cases[file] += 1
        files[file] += t
        funcs[file + "::" + case.get("name").split("[")[0]] += t
    return float(suite.get("time")), cases, dict(files), dict(funcs)


def table(rows, other=None, cases=None, top=None):
    """Lines 'seconds [other diff] [cases] name', longest first."""
    other_of = (other or {}).get
    keys = sorted(set(rows) | set(other or {}),
                  key=lambda k: -max(rows.get(k, 0), other_of(k, 0)))
    out = []
    for k in keys[:top]:
        a = rows.get(k, 0)
        line = f"{a:9.1f}"
        if other is not None:
            line += f" {other_of(k, 0):9.1f} {other_of(k, 0) - a:+9.1f}"
        if cases is not None:
            line += f" {cases[k]:5d}"
        out.append(line + "  " + k)
    return out


def report(path="/tmp/_t1.xml", other_path=None):
    wall, cases, files, funcs = read(path)
    out = [f"{path}: wall {wall:.1f} s, {sum(cases.values())} cases, "
           f"sum {sum(files.values()):.1f} s"]
    o_files = o_funcs = None
    if other_path:
        o_wall, o_cases, o_files, o_funcs = read(other_path)
        out.append(
            f"{other_path}: wall {o_wall:.1f} s, {sum(o_cases.values())} "
            f"cases, sum {sum(o_files.values()):.1f} s (wall x"
            f"{o_wall / wall:.3f}, sum x"
            f"{sum(o_files.values()) / sum(files.values()):.3f})")
        cases = cases | o_cases     # a file's count is the larger of the two
    head = "  seconds" + ("     other      diff" if other_path else "")
    out += ["", head + " cases  file"] + table(files, o_files, cases)
    out += ["", head + "  function (30 longest)"]
    out += table(funcs, o_funcs, top=30)
    return "\n".join(out)


if __name__ == "__main__":
    print(report(*sys.argv[1:3]))
