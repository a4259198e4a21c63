"""Tests of the yardstick itself. Run from the repository's root:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)  # as ``python3 benchmark/run.py`` has it

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
PROCESS_LIMIT_S = 240


def json_lines_of(script: str, *args: str, cwd: str = None):
    """(stdout's JSON lines, exit code, stderr) of ``python3 <script> <args>``
    run as a process of its own with a time limit: a rehearsal, a control or
    a fault driver, whose last line of stdout is its result."""
    import json
    import subprocess

    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    done = subprocess.run([sys.executable, script, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=PROCESS_LIMIT_S)
    rows = [json.loads(line) for line in done.stdout.strip().splitlines() if line.startswith("{")]
    assert rows, done.stderr[-3000:]
    return rows, done.returncode, done.stderr
