"""The bf16 training cell on four chips, as the launcher's data-parallel
step runs it, on four virtual CPU devices in a child process (the device
count is fixed when JAX starts): a sound run agrees with the reference,
and one whose gradient exchange is left out does not."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

CHILD = """
import json, sys
sys.path.insert(0, {tests!r})
from conftest import tiny_spec
from benchmarks.chip import run
from benchmarks.chip.lib import faults
out = {{}}
for name in ("sound", "no_exchange"):
    spec = tiny_spec("train.atacworks-bf16.b64")
    spec["cell"]["chips"] = 4
    if name == "sound":
        r, _ = run.run_cell(spec["cell"]["name"], seed=2**33 + 5,
                            seconds=0.5, trace=False, require_tpu=False,
                            spec=spec)
    else:
        with faults.train_no_exchange():
            r, _ = run.run_cell(spec["cell"]["name"], seed=2**33 + 5,
                                seconds=0.5, trace=False, require_tpu=False,
                                spec=spec)
    out[name] = [r["correct"], r["device"]["count"], r["checks"]]
print(json.dumps(out))
"""


def test_dp4_exchange_is_checked():
    root = Path(__file__).resolve().parents[3]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(root), str(root / "src"), env.get("PYTHONPATH", "")])
    proc = subprocess.run(
        [sys.executable, "-c",
         CHILD.format(tests=str(Path(__file__).parent))],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["sound"][0] and out["sound"][1] == 4, out["sound"]
    assert not out["no_exchange"][0], out["no_exchange"]
