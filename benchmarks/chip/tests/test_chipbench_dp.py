"""The four-chip cell ``train.atacworks-bf16.dp4``, cut down, as the
launcher's data-parallel step runs it, on four virtual CPU devices in a
child process (the device count is fixed when JAX starts): a sound run
agrees with the reference and, traced, reports the exposed collective
time; a run with each fault the cell can have planted in the program (the
gradient exchange left out, the state returned unchanged, half of each
chip's batch left out) does not agree.

A CPU profile holds no device ops, so the traced run reads a hand-made
four-chip trace in place of its own: on each chip an all-reduce
[450, 600] ns under a fusion [0, 500] ns, so 100 ns of it exposed."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

CELL = "train.atacworks-bf16.dp4"
CHILD = """
import json, sys
sys.path.insert(0, {tests!r})
from conftest import tiny_spec
from benchmarks.chip import run
from benchmarks.chip.lib import faults, trace as tr

tr.load = lambda log_dir: [tr.Event("host", "python", tr.WINDOW_SPAN, 0., 1e3)] + [
    tr.Event("device", str(c), name, s, e) for c in range(4)
    for name, s, e in (("fusion.1", 0., 500.), ("all-reduce.7", 450., 600.))]
plants = dict(faults.TRAIN, no_exchange=faults.train_no_exchange)
out = {{}}
for name in ("sound", "traced", *plants):
    spec = tiny_spec({cell!r})
    kw = dict(seed=2**33 + 5, seconds=0.5, trace=name == "traced",
              require_tpu=False, spec=spec)
    if name in plants:
        with plants[name]():
            r, _ = run.run_cell({cell!r}, **kw)
    else:
        r, _ = run.run_cell({cell!r}, **kw)
    out[name] = r
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
         CHILD.format(tests=str(Path(__file__).parent), cell=CELL)],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    sound, traced = out["sound"], out["traced"]
    for r in (sound, traced):
        assert r["correct"] and r["device"]["count"] == 4, r
    assert set(sound["metrics"]) == {"train_segments_per_s", "setup_s"}
    assert traced["metrics"]["dp_collective_exposed_ms"]["value"] == \
        1e3 * 100e-9 / traced["attempted"]
    for fault in ("no_exchange", "state_unchanged", "half_batch"):
        assert not out[fault]["correct"], (fault, out[fault]["checks"])
