"""CI-scale dry-run: the full lower+compile+roofline path on a small forced-
device mesh, one cell per family (subprocess owns its XLA_FLAGS)."""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import sys, json
import jax
from repro.launch import dryrun_lib

from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 4), ("data", "model"))
arch, shape = sys.argv[1], sys.argv[2]
res = dryrun_lib.run_cell(arch, shape, mesh)
print("RESULT " + json.dumps(res.to_json()))
"""


def run_cell(arch, shape):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", SCRIPT, arch, shape],
                       env=env, capture_output=True, text=True, timeout=3000)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


@pytest.mark.slow
@pytest.mark.parametrize("arch,shape", [
    ("internlm2-1.8b", "decode_32k"),      # dense decode path
    ("whisper-base", "train_4k"),          # enc-dec + padded vocab
    ("internvl2-1b", "decode_32k"),        # vlm + padded vocab
])
def test_dryrun_cell_small_mesh(arch, shape):
    d = run_cell(arch, shape)
    assert d["ok"], d["error"]
    if not d["skipped"]:
        assert d["flops_dev"] > 0
        assert d["dominant"] in ("compute", "memory", "collective")
        assert 0 < d["useful_ratio"] <= 2.0
