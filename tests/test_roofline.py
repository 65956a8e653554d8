"""The `make roofline` chain: dry-run artifact production -> roofline table.

The dry-run MUST run as its own process (it forces 512 placeholder host
devices via XLA_FLAGS before any jax import), and benchmarks.roofline reads
its artifact dir from DRYRUN_DIR at import — so both halves run as
subprocesses against a tmpdir, exactly like the Makefile target.
"""
import json
import os
import subprocess
import sys

import pytest


def _run(argv, tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env["DRYRUN_DIR"] = str(tmp_path / "dryrun")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable] + argv, env=env,
                          capture_output=True, text=True, timeout=300,
                          cwd=os.path.dirname(env["PYTHONPATH"]))


@pytest.mark.slow
def test_roofline_chain_renders_nonempty_table(tmp_path):
    out_dir = str(tmp_path / "dryrun")
    r = _run(["-m", "repro.launch.dryrun", "--arch", "internvl2-1b",
              "--shape", "train_4k", "--mesh", "pod", "--out", out_dir],
             tmp_path)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "1 ok, 0 skipped, 0 errors" in r.stdout

    rec = json.load(open(os.path.join(out_dir,
                                      "internvl2-1b__train_4k__pod.json")))
    assert rec["status"] == "ok"
    assert rec["flops_per_device"] > 0
    # xla_cost_analysis must be a flat dict (what compiled.cost_analysis
    # returns on the installed JAX; roofline reads it as one)
    assert isinstance(rec["xla_cost_analysis"], dict)

    r2 = _run(["-m", "benchmarks.roofline"], tmp_path)
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert "internvl2-1b" in r2.stdout  # the table rendered a row
    rows = json.load(open(tmp_path / "roofline_pod.json"))
    assert len(rows) == 1
    assert rows[0]["dominant"] in ("compute", "memory", "collective")
    assert rows[0]["note"]


def test_roofline_empty_artifacts_is_a_clean_failure(tmp_path):
    """No artifacts -> exit 1 with a pointer at the producer, not a crash."""
    r = _run(["-m", "benchmarks.roofline"], tmp_path)
    assert r.returncode == 1
    assert "repro.launch.dryrun" in r.stderr
