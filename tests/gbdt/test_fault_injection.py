"""Fault injection on the training path (SURVEY.md §5 failure handling).

The reference's fault story is Spark task retry + barrier mode; the
analog here is elastic checkpoint/resume: a fit killed WITHOUT warning
(SIGKILL, no atexit, no finally) must resume from its last atomic
checkpoint and reproduce the uninterrupted run bit-for-bit.
"""

import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from mmlspark_tpu.core import faults
from mmlspark_tpu.core.dataframe import DataFrame
from mmlspark_tpu.core.faults import FaultInjected
from mmlspark_tpu.models.gbdt import trainer as trainer_mod
from mmlspark_tpu.models.gbdt.estimators import LightGBMRegressor


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()

_FIT_SCRIPT = """
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from mmlspark_tpu.core.dataframe import DataFrame
from mmlspark_tpu.models.gbdt.estimators import LightGBMRegressor

rng = np.random.default_rng(7)
x = rng.normal(size=(2000, 4))
y = 2.0 * x[:, 0] - x[:, 1] + rng.normal(size=2000) * 0.1
df = DataFrame({{"features": x, "label": y}})
print("FITTING", flush=True)
LightGBMRegressor(numIterations=40, numLeaves=8, maxBin=32,
                  checkpointDir={ckdir!r}, checkpointInterval=4).fit(df)
print("DONE", flush=True)
"""


def _data():
    rng = np.random.default_rng(7)
    x = rng.normal(size=(2000, 4))
    y = 2.0 * x[:, 0] - x[:, 1] + rng.normal(size=2000) * 0.1
    return DataFrame({"features": x, "label": y}), x, y


def test_sigkill_mid_fit_resumes_bit_exact(tmp_path):
    ckdir = str(tmp_path / "ck")
    env = dict(os.environ,
               PYTHONPATH=os.getcwd() + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.Popen(
        [sys.executable, "-c", _FIT_SCRIPT.format(ckdir=ckdir)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env)
    try:
        # hard-kill the trainer as soon as a mid-training checkpoint
        # lands (no cleanup handlers get to run)
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline:
            done = [n for n in os.listdir(ckdir)] if os.path.isdir(ckdir) \
                else []
            if any(n.startswith("checkpoint_") and n.endswith(".txt")
                   for n in done):
                break
            if proc.poll() is not None:
                out, err = proc.communicate()
                pytest.fail(f"fit finished before kill: {err[-500:]}")
            time.sleep(0.05)
        else:
            proc.kill()
            pytest.skip("no checkpoint appeared within timeout")
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == -signal.SIGKILL

    # partial state only: some checkpoints, no finished model
    names = sorted(n for n in os.listdir(ckdir) if n.endswith(".txt"))
    assert names, "kill happened after a checkpoint landed"
    assert f"checkpoint_40.txt" not in names

    df, x, y = _data()
    kw = dict(numIterations=40, numLeaves=8, maxBin=32)
    resumed = LightGBMRegressor(checkpointDir=ckdir, checkpointInterval=4,
                                **kw).fit(df)
    fresh = LightGBMRegressor(**kw).fit(df)
    assert resumed.booster.num_trees == 40
    np.testing.assert_allclose(
        np.asarray(resumed.transform(df)["prediction"]),
        np.asarray(fresh.transform(df)["prediction"]), atol=1e-5)


@pytest.mark.faults
def test_armed_fault_kill_and_resume_bitwise(tmp_path):
    """The deterministic in-process twin of the SIGKILL test (the
    tier-1-safe smoke member of the fault suite): a fit interrupted by
    an armed ``gbdt.train_step`` fault mid-training, then resumed from
    the latest checkpoint, reproduces an uninterrupted run BITWISE."""
    rng = np.random.default_rng(7)
    x = rng.normal(size=(600, 4))
    y = 2.0 * x[:, 0] - x[:, 1] + rng.normal(size=600) * 0.1
    df = DataFrame({"features": x, "label": y})
    kw = dict(numIterations=12, numLeaves=8, maxBin=32,
              checkpointInterval=4)

    ref = LightGBMRegressor(checkpointDir=str(tmp_path / "a"),
                            **kw).fit(df)

    # hit 9 = first iteration of the third segment: checkpoints at 4
    # and 8 are committed, iteration 9's work is lost with the process
    ckb = str(tmp_path / "b")
    with faults.injected("gbdt.train_step", "raise", nth=9):
        with pytest.raises(FaultInjected):
            LightGBMRegressor(checkpointDir=ckb, **kw).fit(df)
    names = sorted(n for n in os.listdir(ckb) if n.endswith(".txt"))
    assert names == ["checkpoint_4.txt", "checkpoint_8.txt"]

    resumed = LightGBMRegressor(checkpointDir=ckb, **kw).fit(df)
    assert resumed.booster.num_trees == 12
    ref_pred = np.asarray(ref.transform(df)["prediction"])
    res_pred = np.asarray(resumed.transform(df)["prediction"])
    np.testing.assert_array_equal(ref_pred, res_pred)


@pytest.mark.faults
def test_checkpoint_write_failure_degrades_not_dies(tmp_path):
    """A failing checkpoint store (armed OSError on checkpoint.write)
    must not kill a healthy fit: training completes, the skip is
    logged once per process, and restart depth just shrinks."""
    from mmlspark_tpu.core.logging_utils import SINK, reset_warn_once
    reset_warn_once()
    SINK.drain()
    rng = np.random.default_rng(3)
    x = rng.normal(size=(300, 3))
    y = x[:, 0] + rng.normal(size=300) * 0.1
    df = DataFrame({"features": x, "label": y})
    ckdir = str(tmp_path / "ck")
    with faults.injected("checkpoint.write", "raise", count=None,
                         exc=OSError("disk full")):
        model = LightGBMRegressor(
            numIterations=6, numLeaves=4, maxBin=16,
            checkpointDir=ckdir, checkpointInterval=3).fit(df)
    assert model.booster.num_trees == 6  # fit survived
    assert not [n for n in os.listdir(ckdir) if n.endswith(".txt")]
    keys = [e.get("key") for e in SINK.drain()
            if e.get("event") == "degradation"]
    assert "gbdt.checkpoint_skip" in keys


@pytest.mark.faults
def test_level_hist_corruption_reaches_the_model(monkeypatch):
    """Arming corrupt on ``gbdt.level_hist`` must change the trained
    model — proof the injection point sits on the real data path (a
    zeroed histogram kills every split)."""
    # the injection point is the native callback; where the library is
    # missing the bindings fall back to numpy behind the same callback
    monkeypatch.setattr(trainer_mod, "native_histogram_available",
                        lambda: True)
    rng = np.random.default_rng(5)
    x = rng.normal(size=(400, 3))
    y = 2.0 * x[:, 0] + rng.normal(size=400) * 0.1
    df = DataFrame({"features": x, "label": y})
    kw = dict(numIterations=3, numLeaves=4, maxBin=16)
    clean = LightGBMRegressor(**kw).fit(df)
    with faults.injected("gbdt.level_hist", "corrupt", count=None,
                         corrupt=lambda h: np.zeros_like(h)):
        broken = LightGBMRegressor(**kw).fit(df)
    clean_pred = np.asarray(clean.transform(df)["prediction"])
    broken_pred = np.asarray(broken.transform(df)["prediction"])
    assert not np.array_equal(clean_pred, broken_pred)
    # with every histogram zeroed no split clears min_gain: the broken
    # model must be the constant base-score predictor
    assert np.allclose(broken_pred, broken_pred[0])


@pytest.mark.faults
def test_every_fault_point_site_is_registered():
    """Fuzzing.scala-style completeness: every production
    ``fault_point("...")`` call site names a registered point, and the
    points the harness advertises are actually threaded through code."""
    import pathlib
    import re

    import mmlspark_tpu
    from mmlspark_tpu.core.faults import KNOWN_POINTS

    root = pathlib.Path(mmlspark_tpu.__file__).parent
    sites = set()
    for p in root.rglob("*.py"):
        if p.name == "faults.py":  # the harness's own docs/examples
            continue
        sites.update(re.findall(r'fault_point\(\s*"([^"]+)"',
                                p.read_text()))
    unregistered = sites - set(KNOWN_POINTS)
    assert not unregistered, f"unregistered fault points: {unregistered}"
    missing = set(KNOWN_POINTS) - sites
    assert not missing, f"registered but never threaded: {missing}"


def test_corrupt_partial_checkpoint_is_invisible(tmp_path):
    """The atomic rename protocol: a torn half-written .tmp file from a
    crashed writer must never be picked up on resume."""
    df, x, y = _data()
    ckdir = str(tmp_path / "ck")
    kw = dict(numIterations=8, numLeaves=8, maxBin=32,
              checkpointDir=ckdir, checkpointInterval=4)
    LightGBMRegressor(**kw).fit(df)
    os.remove(os.path.join(ckdir, "checkpoint_8.txt"))
    # a torn write that never reached os.replace
    with open(os.path.join(ckdir, ".checkpoint_8.tmp"), "w") as fh:
        fh.write("tree\nversion=v4\ngarbage")
    resumed = LightGBMRegressor(**{**kw, "numIterations": 12}).fit(df)
    assert resumed.booster.num_trees == 12
