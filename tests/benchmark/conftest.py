"""The benchmark's tests import the benchmark as a package from the root of
the checkout (tier-1 runs `python -m pytest tests/` from there)."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@pytest.fixture(scope="session")
def linked_root(tmp_path_factory):
    """make(tag) -> a root of its own for a rehearsal: BENCHMARK.json and a
    link to benchmark/. A run empties .bench_run/<workload> under its root,
    so rehearsals of one cell that run side by side (in this file or in
    another worker's) each need theirs."""

    def make(tag: str) -> str:
        root = str(tmp_path_factory.mktemp(tag))
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
        os.symlink(os.path.join(ROOT, "benchmark"),
                   os.path.join(root, "benchmark"))
        return root

    return make


@pytest.fixture(scope="session")
def finish():
    """finish(key, p, timeout=300, may_break=False) -> (result, lines,
    stderr) of a started rehearsal, killed at its own time limit; (None,
    lines, stderr) for one that broke (exit 1, no result line) where that is
    allowed."""

    def wait(key, p, timeout=300, may_break=False):
        try:
            stdout, stderr = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            p.kill()
            p.communicate()
            raise AssertionError(f"rehearsal {key} took over {timeout} s")
        lines = stdout.strip().splitlines()
        if may_break and p.returncode == 1:
            assert not any(ln.startswith("{") for ln in lines)
            return None, lines, stderr
        assert p.returncode == 0, (key, stderr[-2000:])
        return json.loads(lines[-1]), lines, stderr

    return wait
