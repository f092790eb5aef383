"""Multi-device integration tests, run in subprocesses with 8 virtual CPU
devices (XLA_FLAGS must be set before jax init, so these cannot run in the
main pytest process — per the dry-run's own rule)."""

import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(script: str):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(SRC)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, timeout=600, env=env)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    """The sharded (2 data x 4 model) train step computes the same loss as
    single-device execution — the distribution layer is semantics-free."""
    _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.configs.base import get_arch
        from repro.distributed import sharding as shd
        from repro.models import api

        cfg = get_arch("qwen2-1.5b").reduced().replace(
            num_layers=2, d_model=64, d_ff=128, vocab_size=512, head_dim=16)
        params = api.init(jax.random.PRNGKey(0), cfg)
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1),
                                              (4, 64), 0, 512)}
        ref, _ = api.loss_fn(params, cfg, batch)   # single device

        mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4),
                    ("data", "model"))
        with jax.set_mesh(mesh):
            p_sh = shd.named_shardings(params, "train", mesh)
            params_s = jax.tree.map(jax.device_put, params, p_sh)
            b_sh = {"tokens": NamedSharding(mesh, P("data", None))}
            batch_s = jax.tree.map(jax.device_put, batch, b_sh)

            def step(p, b):
                with shd.recipe("train"):
                    return api.loss_fn(p, cfg, b)[0]
            got = jax.jit(step, in_shardings=(p_sh, b_sh))(params_s, batch_s)
        np.testing.assert_allclose(float(got), float(ref), rtol=2e-2)
        print("OK", float(got), float(ref))
    """)


@pytest.mark.slow
def test_elastic_checkpoint_reshard_across_meshes():
    """Save on a (4, 2) mesh, restore onto (2, 4) and single-device — the
    elastic path of the checkpoint manager."""
    _run("""
        import os, tempfile
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.checkpoint.checkpoint import CheckpointManager
        from repro.runtime.elastic import restore_for_mesh
        from repro.distributed.sharding import named_shardings

        tree = {"layers": {"w": jnp.arange(64.0).reshape(8, 8),
                           "b": jnp.ones((8,))}}
        d = tempfile.mkdtemp()
        mgr = CheckpointManager(d, async_save=False)

        devs = np.asarray(jax.devices())
        mesh_a = Mesh(devs.reshape(4, 2), ("data", "model"))
        sh_a = named_shardings(tree, "train", mesh_a)
        tree_a = jax.tree.map(jax.device_put, tree, sh_a)
        mgr.save(5, tree_a)

        mesh_b = Mesh(devs.reshape(2, 4), ("data", "model"))
        restored = restore_for_mesh(mgr, 5, tree, mesh_b, "train")
        np.testing.assert_array_equal(np.asarray(restored["layers"]["w"]),
                                      np.asarray(tree["layers"]["w"]))
        # and plain single-device restore
        plain = mgr.restore(5, tree)
        np.testing.assert_array_equal(np.asarray(plain["layers"]["b"]),
                                      np.asarray(tree["layers"]["b"]))
        print("OK elastic")
    """)


@pytest.mark.slow
def test_dryrun_cell_on_virtual_devices():
    """A reduced-size dry-run cell (lower+compile+HLO analysis) on a small
    virtual mesh — exercises the exact plumbing of launch/dryrun.py."""
    _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.configs.base import get_arch
        from repro.distributed import sharding as shd
        from repro.launch import hlo_analysis
        from repro.models import api
        from repro.train import optimizer as opt_lib

        cfg = get_arch("granite-moe-1b-a400m").reduced()
        mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4),
                    ("data", "model"))
        with jax.set_mesh(mesh):
            specs = {"tokens": jax.ShapeDtypeStruct((4, 128), jnp.int32)}
            p_specs = jax.eval_shape(partial(api.init, cfg=cfg),
                                     jax.random.PRNGKey(0))
            o_specs = jax.eval_shape(opt_lib.init_state, p_specs)
            p_sh = shd.named_shardings(p_specs, "train", mesh)
            o_sh = shd.named_shardings(o_specs, "train", mesh)
            b_sh = {"tokens": NamedSharding(mesh, P("data", None))}
            ocfg = opt_lib.OptimizerConfig()

            def train_step(p, o, b):
                with shd.recipe("train"):
                    (l, m), g = jax.value_and_grad(
                        lambda pp: api.loss_fn(pp, cfg, b), has_aux=True)(p)
                    p, o, _ = opt_lib.apply_updates(ocfg, p, o, g)
                    return p, o, l

            fn = jax.jit(train_step, in_shardings=(p_sh, o_sh, b_sh),
                         donate_argnums=(0, 1))
            compiled = fn.lower(p_specs, o_specs, specs).compile()
            res = hlo_analysis.analyze(compiled.as_text())
            assert res["dot_flops_per_device"] > 0
            ma = compiled.memory_analysis()
            # peak_memory_in_bytes only exists on newer jaxlib
            peak = getattr(ma, "peak_memory_in_bytes", None)
            if peak is None:
                peak = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
                        + ma.output_size_in_bytes)
            assert peak > 0
            print("OK dryrun-mini", res["dot_flops_per_device"])
    """)
