"""``chip_smoke.py`` off the chip: its rehearsal (a tiny network on the
CPU) runs every check the chip run makes and prints no ok line, and a
plain run without a TPU exits nonzero with no ok line.

Each case is a child process, as the script is run.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke(*args, devices=1):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env.pop("PYTHONPATH", None)             # the script finds src/ itself
    return subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py"),
                           *args], capture_output=True, text=True,
                          timeout=300, env=env, cwd=REPO)


def _has_ok_line(proc):
    return any(ln.startswith('{"ok"') for ln in proc.stdout.splitlines())


@pytest.mark.parametrize("args,devices", [
    (("--rehearse",), 1),
    (("--rehearse", "--four-chips"), 4),
], ids=["one_chip", "four_chips"])
def test_rehearsal_passes_without_an_ok_line(args, devices):
    proc = _smoke(*args, devices=devices)
    assert proc.returncode == 0, proc.stderr
    assert "rehearsal passed" in proc.stdout
    assert not _has_ok_line(proc)


def test_no_tpu_exits_nonzero_without_an_ok_line():
    proc = _smoke()
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not _has_ok_line(proc)
