"""Examples must keep running — they are the user-facing front door.

Two fast ones run as subprocesses (fresh interpreter, the way a user
would); the heavier ones are exercised by the suites covering the same
paths.
"""
import os
import pathlib
import subprocess
import sys

import pytest

_EXAMPLES = pathlib.Path(__file__).resolve().parents[1] / "examples"


@pytest.mark.parametrize(
    "script", ["streaming_out_of_core.py", "text_pipeline.py",
               "multihost_mesh.py"]
)
def test_example_runs(script):
    proc = subprocess.run(
        [sys.executable, str(_EXAMPLES / script)],
        capture_output=True, text=True, timeout=420,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": os.environ.get("HOME", "/tmp")},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]


# -- chip_smoke.py: the proof that the system starts on the chip ---------

_SMOKE = _EXAMPLES.parent / "chip_smoke.py"


def _run_smoke(*flags):
    return subprocess.run(
        [sys.executable, str(_SMOKE), *flags],
        capture_output=True, text=True, timeout=420, cwd=_SMOKE.parent,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )


def test_chip_smoke_without_a_chip_fails_before_any_work():
    # no accelerator, no --rehearsal: never a CPU result under a device's
    # name — non-zero exit, nothing on stdout, and the failure is jax
    # refusing the pinned platform, not a phase
    proc = _run_smoke()
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "tpu" in proc.stderr.lower()


@pytest.mark.slow  # ~10 s: the whole script at toy size (D10: tier-1 is over budget)
def test_chip_smoke_rehearsal_is_labelled_and_passes():
    import json

    proc = _run_smoke("--rehearsal")
    assert proc.returncode == 0, proc.stderr[-2000:]
    report, verdict = map(json.loads, proc.stdout.strip().splitlines()[-2:])
    assert report["rehearsal"] is True
    assert set(report["phases"]) == {"fit", "stream", "serve"}
    # the last line is what the chip check reads: these keys and no other
    assert set(verdict) == {"ok", "device"} and verdict["ok"] is True
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    assert verdict["device"]["platform"] == "cpu"
    assert isinstance(verdict["device"]["kind"], str)
    assert type(verdict["device"]["count"]) is int
    assert report["device"] == verdict["device"]
