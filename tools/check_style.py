#!/usr/bin/env python
"""Source hygiene checks (reference tools/codestyle/ + check_file_diff_
approvals.sh role, scoped): line length, tabs, trailing whitespace,
accidental debug prints in the package, and that every test file is
collected by pytest's naming convention."""

from __future__ import annotations

import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MAX_LEN = 100


def check() -> int:
    bad = 0
    for root, dirs, files in os.walk(os.path.join(REPO, "paddle1_tpu")):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for fn in files:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(root, fn)
            rel = os.path.relpath(path, REPO)
            for i, line in enumerate(open(path), 1):
                stripped = line.rstrip("\n")
                if "\t" in stripped:
                    print(f"{rel}:{i}: tab character")
                    bad += 1
                if len(stripped) > MAX_LEN:
                    print(f"{rel}:{i}: line longer than {MAX_LEN}")
                    bad += 1
                if re.match(r"\s*import pdb|\s*pdb\.set_trace", stripped):
                    print(f"{rel}:{i}: pdb left in source")
                    bad += 1
    for fn in os.listdir(os.path.join(REPO, "tests")):
        if fn.endswith(".py") and fn not in ("conftest.py", "op_test.py", "decoder_cases.py") \
                and not fn.startswith("test_"):
            print(f"tests/{fn}: not collected (must start with test_)")
            bad += 1
    print(f"check_style: {'OK' if not bad else f'{bad} issue(s)'}")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(check())
