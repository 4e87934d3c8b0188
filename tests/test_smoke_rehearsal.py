"""chip_smoke.py without a chip: it must fail, and say so.

The sandbox is where a chip run is rehearsed (on-chip-measurement guide
§2): ``--rehearse`` walks every phase's control flow at toy size on the
CPU with the Pallas interpreter. Whatever happens on a machine whose
platform is not ``tpu``, the script exits non-zero and never prints an
``"ok": true`` line — a pass is a chip's to give.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def run_smoke(*argv, cwd=REPO, script=SMOKE, devices=1, timeout=600):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env.pop("PYTHONPATH", None)  # the script finds the repo beside itself
    proc = subprocess.run(
        [sys.executable, script, *argv], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=timeout,
    )
    lines = proc.stdout.splitlines()
    assert proc.returncode != 0, proc.stdout[-2000:]
    assert not any('"ok": true' in ln for ln in lines), proc.stdout[-2000:]
    return lines, proc


def test_no_chip_fails_at_the_device_gate():
    lines, proc = run_smoke()
    assert json.loads(lines[-1]) == {"ok": False, "failed": "train"}
    assert "device gate: platform is tpu" in proc.stderr
    # it stopped there: no phase ran on the CPU under a device's name
    assert not any("passed" in ln for ln in lines)


def test_alone_in_a_directory_it_fails(tmp_path):
    """A directory that holds chip_smoke.py and nothing else of the repo."""
    script = shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    lines, proc = run_smoke(cwd=str(tmp_path), script=str(script))
    assert "No module named 'edl_tpu'" in proc.stderr


@pytest.mark.parametrize(
    "argv,devices,phases",
    [
        ((), 1, ("train", "serve", "serve_h8", "process")),
        (("--chips", "4"), 4, ("elastic4",)),
    ],
    ids=["one_chip", "four_chips"],
)
def test_rehearsal_walks_every_phase_and_never_passes(argv, devices, phases):
    lines, proc = run_smoke("--rehearse", *argv, devices=devices)
    tail = proc.stdout[-3000:] + proc.stderr[-3000:]
    for phase in phases:
        assert any(ln.startswith(f"[{phase}] passed") for ln in lines), tail
        # every phase saw, and said, that this is not the chip (the
        # process phase stays off JAX: its worker reports the platform)
        seen = ('worker devices: {"platform": "cpu"' if phase == "process"
                else "device gate FAILED")
        assert any(seen in ln for ln in lines
                   if ln.startswith(f"[{phase}]")), tail
    # with the option: that path and what it is compared with, no other
    ran = {ln.split("]")[0][1:] for ln in lines if ln.startswith("[")}
    assert ran == set(phases)
    assert json.loads(lines[-1]) == {
        "ok": False, "failed": "rehearsal (never a pass)",
    }
