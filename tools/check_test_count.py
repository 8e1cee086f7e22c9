#!/usr/bin/env python
"""Collected-test-count regression gate (CI).

Runs pytest collection and fails (exit 1) when the number of collected
tests drops below ``MIN_COLLECTED_TESTS`` (env var; default = the count
recorded when the gate was introduced). "All green" is meaningless if a
refactor silently stopped a test file from importing or collecting —
pytest reports collection ERRORS loudly, but a file dropped from testpaths
or skipped by a rename disappears without one. The floor only ratchets UP:
raise the default (and the pin in .github/workflows/ci.yml) when tests are
added; lowering it is a reviewed decision, not an accident.

Usage:  PYTHONPATH=src python tools/check_test_count.py
"""
from __future__ import annotations

import os
import re
import subprocess
import sys
from pathlib import Path

DEFAULT_MIN = 416  # the collected count at the last ratchet; raise it with new tests


def main() -> int:
    floor = int(os.environ.get("MIN_COLLECTED_TESTS", DEFAULT_MIN))
    repo = Path(__file__).resolve().parent.parent
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "--collect-only", "-q"],
        capture_output=True, text=True, cwd=repo)
    tail = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() else ""
    m = re.search(r"(\d+) tests? collected", tail)
    if proc.returncode != 0 or not m:
        print(f"[check_test_count] FAIL — collection errored "
              f"(rc={proc.returncode}): {tail}")
        sys.stderr.write(proc.stderr[-2000:])
        return 1
    count = int(m.group(1))
    if count < floor:
        print(f"[check_test_count] FAIL — {count} tests collected, floor "
              f"is {floor}: a test file stopped collecting, or the floor "
              f"needs a reviewed lowering")
        return 1
    print(f"[check_test_count] OK — {count} tests collected "
          f"(floor {floor})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
