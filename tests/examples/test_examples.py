"""Every examples/ script must run end to end (the nbtest analog:
the reference executes its website notebooks in CI,
DatabricksUtilities.scala / build.sbt:365-370 — examples that aren't
executed rot)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
EXAMPLES = os.path.join(REPO, "examples")

SCRIPTS = sorted(f for f in os.listdir(EXAMPLES)
                 if f.endswith(".py") and f[0].isdigit())


def test_all_examples_are_covered():
    # a new example must appear here (picked up by the glob) and run
    assert len(SCRIPTS) >= 5


@pytest.mark.parametrize("script", SCRIPTS)
def test_example_runs(script):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # examples must not inherit the test process's virtual-device
    # forcing; 05 spawns its own cluster, others run single-device
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES, script)],
        cwd=EXAMPLES, capture_output=True, text=True, timeout=900,
        env=env)
    assert r.returncode == 0, (
        f"{script} failed:\n{r.stdout[-3000:]}\n{r.stderr[-3000:]}")
    assert f"OK {script[:-3]}" in r.stdout