"""chip_smoke.py's contract where there is no chip: without the
rehearsal flag it refuses to run and names the platform it found; the
tiny rehearsal (Pallas interpreted) drives every phase — the mesh leg
included, over four virtual CPU devices — and can never print the pass
line."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PASS_PREFIX = '{"ok": true'


def _run(*args, devices=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # not the test process's 8 virtual devices
    env.pop("XLA_FLAGS", None)
    if devices:
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=850)


def test_no_chip_no_flag_fails_and_names_the_platform():
    r = _run()
    assert r.returncode != 0
    assert "'cpu'" in r.stderr and "TPU" in r.stderr
    assert r.stdout.strip() == ""          # no result of any kind


def test_rehearsal_runs_every_phase_and_cannot_pass():
    r = _run("--rehearse", "--rows", "12000", "--trees", "2", devices=4)
    assert r.returncode == 0, r.stderr[-3000:]
    lines = [json.loads(line) for line in r.stdout.splitlines()
             if line.startswith("{")]
    assert not any(line.startswith(PASS_PREFIX)
                   for line in r.stdout.splitlines())
    assert lines[-1] == {"ok": False, "rehearsal": "completed",
                         "device": {"platform": "cpu", "kind": "cpu",
                                    "count": 4}}
    phases = {line["phase"]: line for line in lines if "phase" in line}
    assert list(phases) == ["start", "fit_transform", "serve", "kernel",
                            "mesh_fit_transform"]
    single = phases["fit_transform"]["hist_stats"]
    assert (single["hist_formulation"], single["tree_mode"],
            single["pallas_interpret"]) == ("pallas", "serial", True)
    assert phases["serve"]["replies_bitwise"] is True
    assert phases["serve"]["generic_batches"] == 0
    assert phases["kernel"]["counts_exact"] is True
    mesh = phases["mesh_fit_transform"]
    assert mesh["hist_stats"]["tree_mode"] == "data_sharded"
    assert mesh["hist_stats"]["hist_shard"] == "on"
    assert mesh["hist_stats"]["binned_rows_per_device"] == [3000] * 4
    assert mesh["shard"]["shard_rules_dp"] == 4
