"""Dry-run smoke (subprocess: needs its own XLA_FLAGS before jax init).

Full production meshes (16x16 and 2x16x16) are exercised by
``python -m repro.launch.dryrun --all`` (artifacts in benchmarks/artifacts);
these tests prove the same programs lower + compile on debug meshes with 8
placeholder devices, including a multi-pod (2,2,2) mesh, quickly enough for
CI.
"""

import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
import jax
from repro.launch.dryrun import run_one
from repro.launch.mesh import make_debug_mesh

arch, shape, multipod = sys.argv[1], sys.argv[2], sys.argv[3] == "1"
mesh = (make_debug_mesh(2, 2, multi_pod=True) if multipod
        else make_debug_mesh(4, 2))
rec = run_one(arch, shape, mesh=mesh, out_dir="/tmp/repro_dryrun_test")
print("RESULT::" + json.dumps({
    "flops": rec["corrected_flops"],
    "coll": rec["collective_bytes"],
    "bottleneck": rec["roofline"]["bottleneck"],
    "ratio": rec["useful_flops_ratio"],
}))
"""


def _run(arch, shape, multipod=False, timeout=520):
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT, arch, shape, "1" if multipod else "0"],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT::")][0]
    return json.loads(line[len("RESULT::"):])


@pytest.mark.slow
def test_dryrun_train_single_pod():
    rec = _run("smollm-360m", "train_4k")
    assert rec["flops"] > 1e14
    assert rec["coll"] > 0          # the DeCaPH secure-sum collectives exist
    # MODEL_FLOPS/HLO ratio: attention + DP overhead push it well below 1 on
    # small-d models; just assert it is a sane fraction.
    assert 0.005 < rec["ratio"] < 5.0


@pytest.mark.slow
def test_dryrun_train_multi_pod():
    rec = _run("olmo-1b", "train_4k", multipod=True)
    assert rec["flops"] > 1e14
    assert rec["coll"] > 0


@pytest.mark.slow
def test_dryrun_decode_long_context_ssm():
    rec = _run("rwkv6-3b", "long_500k")
    assert rec["flops"] > 1e8


@pytest.mark.slow
def test_dryrun_decode_whisper():
    rec = _run("whisper-small", "decode_32k")
    assert rec["flops"] > 1e8


@pytest.mark.slow
def test_dryrun_moe_prefill():
    rec = _run("qwen3-moe-30b-a3b", "prefill_32k")
    assert rec["coll"] > 0          # expert all-to-alls / gathers present
