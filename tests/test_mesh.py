"""Every mesh factory in ``launch/mesh.py`` builds Auto axes, and the
``ghost_norm`` kernel runs on such a mesh.

JAX's ``make_mesh`` defaults to Explicit axes, under which the ``shard``
backend's placed-operand programs and the dry-run fail to trace.  The
factories run in a subprocess with 512 forced host devices, enough for the
production meshes.
"""

import json
import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import inspect, json
from repro.launch import mesh as mesh_lib

built = {
    "production": mesh_lib.make_production_mesh(),
    "production-multi-pod": mesh_lib.make_production_mesh(multi_pod=True),
    "debug": mesh_lib.make_debug_mesh(),
    "debug-multi-pod": mesh_lib.make_debug_mesh(2, 1, multi_pod=True),
    "host-data": mesh_lib.make_host_data_mesh(4),
    "host": mesh_lib.make_host_mesh(),
}
factories = sorted(n for n, f in inspect.getmembers(mesh_lib,
                                                     inspect.isfunction)
                   if n.startswith("make_") and f.__module__ == mesh_lib.__name__)
print("RESULT" + json.dumps({
    "factories": factories,
    "meshes": {k: {"shape": list(m.devices.shape),
                   "types": [t.name for t in m.axis_types]}
               for k, m in built.items()},
}))
"""


def test_every_mesh_factory_builds_auto_axes():
    env = dict(os.environ, PYTHONPATH=SRC + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT")]
    report = json.loads(line[0][len("RESULT"):])
    # a new factory must be added to the script above
    assert report["factories"] == ["make_debug_mesh", "make_host_data_mesh",
                                   "make_host_mesh", "make_production_mesh"]
    for name, m in report["meshes"].items():
        assert m["types"] == ["Auto"] * len(m["shape"]), name
    assert report["meshes"]["host"]["shape"] == [256, 2]
    assert report["meshes"]["debug-multi-pod"]["shape"] == [2, 2, 1]


_KERNEL_ON_MESH = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.kernels.ghost_norm.ops import ghost_norm
from repro.kernels.ghost_norm.ref import ghost_norm_ref
from repro.launch.mesh import make_debug_mesh

mesh = make_debug_mesh(2, 1, multi_pod=True)
key = jax.random.key(0)
a = jax.random.normal(jax.random.fold_in(key, 1), (4, 2, 32, 16))
g = 0.1 * jax.random.normal(jax.random.fold_in(key, 2), (4, 2, 32, 8))
silos = NamedSharding(mesh, P(("pod", "data")))
kernel = jax.vmap(lambda x, y: ghost_norm(x, y, block_s=16, block_t=16,
                                          force_kernel=True))
with jax.set_mesh(mesh):
    out = jax.jit(kernel)(jax.device_put(a, silos), jax.device_put(g, silos))
ref = jax.vmap(ghost_norm_ref)(a, g)
print("RESULT" + str(float(np.abs(np.asarray(out) - np.asarray(ref)).max()
                           / np.abs(np.asarray(ref)).max())))
"""


def test_ghost_norm_kernel_runs_per_device_on_a_mesh():
    """The hospital axis split over 4 devices: the kernel runs in a
    shard_map on each device's examples and matches the oracle."""
    env = dict(os.environ, PYTHONPATH=SRC + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", _KERNEL_ON_MESH], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT")]
    assert float(line[0][len("RESULT"):]) < 2e-5
