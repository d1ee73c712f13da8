"""The held four-chip what-if cell at a tiny size on four virtual CPU devices, in a child
process (the device count is fixed when JAX starts): it runs and comes out
correct, and leaving out the exchange of one chip's lanes makes it not
correct."""
import json
import pathlib
import subprocess
import sys

SCRIPT = pathlib.Path(__file__).parent / "sharded_run.py"


def _run(*flags):
    r = subprocess.run([sys.executable, str(SCRIPT), *flags], capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_sharded_cell_runs_and_is_correct():
    out = _run()
    assert out["correct"] is True and out["failed"] == 0 and out["device"]["count"] == 4
    assert out["metrics"]["scenarios_per_s"]["value"] > 0


def test_exchange_left_out_is_caught():
    assert _run("--drop-chip")["correct"] is False
