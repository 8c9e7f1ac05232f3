"""Sharded page bank over REAL (faked) devices: mesh placement and
shard_map local reads need more than one device, so the checks run in a
subprocess that forces ``--xla_force_host_platform_device_count=4``
before importing jax (this process's backend is already initialized and
cannot be re-split).  See ``_sharded_worker.py`` for the checks."""
import json
import os
import subprocess
import sys

import pytest

WORKER = os.path.join(os.path.dirname(__file__), "_sharded_worker.py")


@pytest.fixture(scope="module")
def worker_results():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, WORKER], capture_output=True,
                         text=True, timeout=900, env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    line = [l for l in out.stdout.splitlines()
            if l.startswith("RESULTS_JSON:")]
    assert line, out.stdout + out.stderr[-2000:]
    return json.loads(line[-1][len("RESULTS_JSON:"):])


@pytest.mark.parametrize("check", [
    "bank_placed_over_mesh", "mesh_streams_bitwise",
    "mesh_prefix_bitwise", "local_read_greedy_streams",
    "local_read_chunked_streams", "mesh_kernel_streams"])
def test_sharded_device_check(worker_results, check):
    res = worker_results.get(check)
    assert res is not None, f"check {check} did not run: {worker_results}"
    assert res["ok"], res
