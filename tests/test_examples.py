"""Smoke-run every ``examples/`` script so frontend API churn can't
silently break them (none of them was executed by the suite before)."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
EXAMPLES = REPO / "examples"

#: every example with its expected stdout fingerprints (cheap sanity that
#: the script not only exited 0 but did its job)
CASES = {
    "quickstart.py": ("Table II", "improvement", "Device scale"),
    "pim_pipeline.py": ("NTT", "bit-exact"),
    "serve_batch.py": ("glm4-9b", "falcon-mamba-7b"),
    "trace_viewer.py": ("moe-decode", ".trace.json", "ui.perfetto.dev"),
}


@pytest.mark.slow
@pytest.mark.parametrize("script", sorted(CASES))
def test_example_runs(script):
    env = dict(os.environ)
    # importing launch/dryrun.py in this process sets 512 host devices; an
    # example runs on the host's own devices
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = str(REPO / "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    proc = subprocess.run(
        [sys.executable, str(EXAMPLES / script)],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600)
    assert proc.returncode == 0, \
        f"{script} failed:\n{proc.stdout}\n{proc.stderr}"
    for token in CASES[script]:
        assert token in proc.stdout, \
            f"{script} output missing {token!r}:\n{proc.stdout}"


def test_every_example_is_covered():
    """A new example script must be added to CASES (or consciously skipped)."""
    scripts = {p.name for p in EXAMPLES.glob("*.py")}
    known_uncovered = {"train_lm.py"}   # full training loop: covered by
    #   tests/test_train_infra.py at reduced scale; too slow as a subprocess
    assert scripts - known_uncovered == set(CASES)
