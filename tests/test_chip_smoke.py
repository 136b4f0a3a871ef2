"""chip_smoke.py on the CPU: both phases at the smoke config, and no
result printed where there is no TPU or no program beside the script."""

import os
import shutil
import subprocess
import sys

import pytest

import chip_smoke
from tests.helpers import ROOT, run_case


def test_one_chip_phase_matches_reference():
    r = chip_smoke.serve_one_chip(smoke=True)
    assert r["tokens"] == r["tokens_checked"] == chip_smoke.ROWS * chip_smoke.MAX_NEW
    assert r["worst_deficit_ulps"] <= chip_smoke.MARGIN_ULPS


def test_four_chip_phase_on_virtual_devices():
    run_case("chip_smoke_four", ndev=4, timeout=600)


@pytest.mark.parametrize("alone", [False, True])
def test_no_result_without_tpu(tmp_path, alone):
    """The CPU is not a TPU; a copy of the script without the repository
    cannot import the program. Either way: non-zero exit, no result."""
    script = os.path.join(ROOT, "chip_smoke.py")
    if alone:
        script = shutil.copy(script, tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, script], capture_output=True,
                          text=True, timeout=300, env=env,
                          cwd=tmp_path if alone else ROOT)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout, proc.stdout
