"""``trinity-mini-fit-8k-1chip`` rehearsed in tier-1: the untraced case
of ``benchmark/tests/test_trinity_rehearsal.py`` (everything of
``run.py`` but its look for a chip, at the published widths on layers
2-3, 2 experts, 256 vocabulary rows and 96 tokens under a window of 32,
``correct`` against the plain reference) in a child process that sees ONE
device: the cell is one chip's, and this suite's pool of eight would hand
``fit()`` eight rows a step where the harness regenerates one. In a file
of its own so that no other file's worker waits for it; the traced case
runs with the benchmark's own tests."""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_a_rehearsal_of_the_trinity_cell_on_one_device_is_correct():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-p", "no:randomly",
         "benchmark/tests/test_trinity_rehearsal.py", "-k", "False"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    tail = done.stdout[-3000:] + done.stderr[-2000:]
    assert done.returncode == 0, tail
    assert "1 passed" in done.stdout, tail
