"""Run the four-chip cell cut to a tiny size on four virtual CPU devices,
sound and with the cross-shard exchange left out; print both verdicts.
A process of its own: the device count is fixed before JAX starts."""
import json
import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "perfbench"),
                str(HERE.parents[1] / "src")]

import pytest  # noqa: E402

from faults import no_exchange  # noqa: E402
from tiny import run_tiny  # noqa: E402

NAME = "sent140-lstm.sharded4-k128"
out = {}
res, _ = run_tiny(NAME)
out["sound"] = {"correct": res["correct"], "count": res["device"]["count"],
                "checks": res["checks"]}
with pytest.MonkeyPatch.context() as mp:
    no_exchange(mp)
    res, _ = run_tiny(NAME)
out["no_exchange"] = {"correct": res["correct"], "checks": res["checks"]}
print(json.dumps(out))
