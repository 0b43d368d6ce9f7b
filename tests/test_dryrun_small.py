"""Integration: the dry-run machinery on a small forced-device mesh.

Runs in a subprocess so the 16 fake CPU devices don't leak into the main
pytest process (jax locks the device count at first init).
"""

import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import json, jax
import repro.launch.dryrun as dr

AUTO = jax.sharding.AxisType.Auto

def small_mesh(multi_pod=False):
    if multi_pod:
        return jax.make_mesh((2, 2, 4), ("pod", "data", "model"),
                             axis_types=(AUTO,) * 3)
    return jax.make_mesh((4, 4), ("data", "model"), axis_types=(AUTO,) * 2)

dr.make_production_mesh = small_mesh
out = []
for arch, shape, mp in [
    ("granite-3-2b", "train_4k", False),
    ("granite-3-2b", "decode_32k", False),
    ("olmoe-1b-7b", "train_4k", True),
]:
    rec = dr.run_cell(arch, shape, mp, "")
    out.append({k: rec.get(k) for k in
                ("arch", "shape", "status", "error", "la_flops_per_device",
                 "la_link_bytes_per_device", "dominant",
                 "useful_flops_ratio")})
print("RESULT::" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ, PYTHONPATH="src")
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=1200,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT::")]
    assert line, proc.stdout[-2000:]
    return json.loads(line[0][len("RESULT::"):])


def test_all_cells_compile(results):
    for rec in results:
        assert rec["status"] == "ok", rec


def test_flops_and_collectives_recorded(results):
    for rec in results:
        assert rec["la_flops_per_device"] > 0
        assert rec["dominant"] in ("compute_s", "memory_s", "collective_s")
    train = results[0]
    assert train["la_link_bytes_per_device"] > 0   # sharded training communicates


def test_useful_ratio_sane(results):
    train = results[0]
    assert 0.2 < train["useful_flops_ratio"] < 3.0, train
