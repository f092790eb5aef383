"""A cell on a (2, 2) mesh, on four virtual CPU devices in a subprocess
(the device count is fixed before JAX starts): the weights drawn into the
program's shardings equal the one-device draw byte for byte, the
executor takes them as they are, and a whole run is correct and reports
every metric."""

import os
import subprocess
import sys
import textwrap

import tiny

SCRIPT = """
import copy, os, sys, tempfile, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
import numpy as np
import tiny
from bench import harness, spec, weights

harness.require_accelerator = lambda chips: jax.devices()
harness.lookup_peaks = lambda kind: tiny.PEAKS
harness.enable_compile_cache = lambda root: ""
assert len(jax.devices()) == 4
cell = copy.deepcopy(tiny.CELL)
cell["serve"]["mesh_shape"] = [2, 2]
root = tiny.make_root(sys.argv[1], cell=cell, chips=4)
sp = spec.cell(root, "tiny.small")
mesh, devices = harness.cell_mesh(harness.mesh_shape(sp), jax.devices())
assert mesh.devices.shape == (2, 2) and len(devices) == 4
model = harness.served_model(root, sp, mesh)

seed = 2**33 + 5
one = weights.build(sp["conf"], seed)
many = harness.draw_weights(sp, model, seed)
for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(many)):
    assert a.dtype == b.dtype and a.shape == b.shape
    assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
wq = many["layers"]["attn"]["wq"]["w"]
assert len(wq.sharding.device_set) == 4 and not wq.sharding.is_fully_replicated
server = harness.Server(sp, many, trace=False)
assert server.engine.executor.mesh.devices.shape == (2, 2)
placed = jax.tree.leaves(server.engine.executor.params)
assert all(x is y for x, y in zip(placed, jax.tree.leaves(many)))
server.free()
del one, many, placed

out, sp = harness.run_cell(root, "tiny.small", 4242, 3.0, True,
                           time.perf_counter())
limit = sp["cell"]["check"]["widest_logit_gap_limit"]
assert out["e2e"]["n_failed"] == 0, out["e2e"]
assert out["widest_gap"] is not None and out["widest_gap"] <= limit, out
for name in ("ttft_p95_ms", "itl_p99_ms", "tokens_per_s"):
    assert out["e2e"][name] > 0, name
assert out["setup_s"] > 0
read = set(out["layer"])
for name in ("queue_wait_p95_ms", "host_ms_per_step", "prefill_share",
             "mfu.decode", "mfu.prefill", "device_idle_share",
             "ttft_p95_ms.longprompt", "frontdoor_ingress_p95_ms",
             "loop_pickup_p95_ms", "sse_egress_p99_ms",
             "loop_self_ms_per_step"):
    assert name in read, (name, read)
assert 0 < out["busy_s"] <= out["window_s"]
print("mesh ok", out["widest_gap"])
"""


def test_mesh_cell_draws_sharded_weights_and_runs_correct(tmp_path):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(os.path.abspath(__file__)), tiny.REPO,
         os.path.join(tiny.REPO, "src")])
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(SCRIPT),
                        str(tmp_path)], capture_output=True, text=True,
                       env=env, timeout=600)
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr}"
    assert "mesh ok" in p.stdout
