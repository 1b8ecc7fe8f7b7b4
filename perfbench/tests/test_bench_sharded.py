"""The four-chip cell on four virtual CPU devices: the reference agrees
with the sharded trainer, and leaving the exchange between chips out
turns ``correct`` false."""
import json
import subprocess
import sys
from pathlib import Path


def test_sharded_cell_and_its_exchange_fault():
    p = subprocess.run([sys.executable, str(Path(__file__).parent
                                            / "sharded_run.py")],
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["sound"]["count"] == 4
    assert out["sound"]["correct"], out["sound"]["checks"]
    assert not out["no_exchange"]["correct"], out["no_exchange"]["checks"]
