"""Each cell runs at a tiny size through the same functions as on the
chip (`run.run_cell`, its driver, the reference comparison), with the
device check given a CPU record; and a non-TPU device is refused."""
import os
import subprocess
import sys

import pytest

from benchtest import BENCH, CELLS, CPU, ROOT



@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_and_is_correct(tiny, runner, name):
    out = runner.run_cell(tiny, name, 2**31 + 11, 0.01, False, device=CPU)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    want = {m["name"] for m in tiny.metrics_of(name, "end_to_end")}
    assert set(out["metrics"]) == want and "setup_s" in want
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}


def test_a_device_that_is_not_a_tpu_is_refused():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "TPU" in proc.stderr
    assert '"correct"' not in proc.stdout
