"""Multi-device sharding correctness: runs subprocesses with 8 fake host
devices (device count locks at first jax init, so these can't share the main
test process)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str) -> str:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(ROOT, "src"))
    # every mesh in these scripts has Auto axes (see launch/mesh.py)
    code = "from repro.launch.mesh import auto_mesh\n" + textwrap.dedent(code)
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, env=env,
                         timeout=560)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_sharded_train_step_matches_single_device():
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.models.zoo import ModelBundle
        from repro.configs import get_config
        from repro.dist.sharding import make_mesh_ctx
        from repro.optim import adamw_init

        cfg = get_config("qwen2-72b", smoke=True)
        b = ModelBundle(cfg)
        params = b.init(jax.random.PRNGKey(0))
        opt = adamw_init(params)
        B, L = 4, 32
        batch = {"tokens": jnp.ones((B, L), jnp.int32),
                 "labels": jnp.ones((B, L), jnp.int32),
                 "loss_mask": jnp.ones((B, L), jnp.float32)}
        ref_loss = float(jax.jit(b.loss_fn(None))(params, batch))

        mesh = auto_mesh((2, 4), ("data", "model"))
        ctx = make_mesh_ctx(mesh)
        with jax.set_mesh(mesh):
            sharded = jax.jit(b.loss_fn(ctx))
            got = float(sharded(params, batch))
        assert abs(got - ref_loss) < 5e-2, (got, ref_loss)
        print("loss match:", got, ref_loss)
    """))


def test_sharded_moe_matches_local():
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.models.zoo import ModelBundle
        from repro.configs import get_config
        from repro.dist.sharding import make_mesh_ctx

        cfg = get_config("mixtral-8x7b", smoke=True)
        b = ModelBundle(cfg)
        params = b.init(jax.random.PRNGKey(1))
        B, L = 4, 32
        batch = {"tokens": (jnp.arange(B * L, dtype=jnp.int32).reshape(B, L)
                            % cfg.vocab),
                 "labels": jnp.ones((B, L), jnp.int32),
                 "loss_mask": jnp.ones((B, L), jnp.float32)}
        ref = float(jax.jit(b.loss_fn(None))(params, batch))
        mesh = auto_mesh((2, 4), ("data", "model"))
        ctx = make_mesh_ctx(mesh)
        with jax.set_mesh(mesh):
            got = float(jax.jit(b.loss_fn(ctx))(params, batch))
        # MoE capacity differs between 1-shard and 8-shard dispatch
        # (per-shard capacity rounding); tolerance reflects that.
        assert abs(got - ref) / ref < 0.05, (got, ref)
        print("moe loss:", got, ref)
    """))


def test_multipod_mesh_axes():
    print(_run("""
        import jax
        from repro.launch.mesh import make_production_mesh
        # 8 fake devices can't build 512; verify the axis logic via shape math
        from repro.dist.sharding import make_mesh_ctx
        mesh = auto_mesh((2, 2, 2), ("pod", "data", "model"))
        ctx = make_mesh_ctx(mesh)
        assert ctx.multi_pod and ctx.dp == 4 and ctx.tp == 2
        assert ctx.dp_axes == ("pod", "data")
        print("multipod ctx ok")
    """))


def test_elastic_restore_across_mesh_shapes(tmp_path):
    print(_run(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import CheckpointManager

        mgr = CheckpointManager({str(tmp_path)!r})
        mesh_a = auto_mesh((8,), ("data",))
        tree = {{"w": jax.device_put(
            jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
            NamedSharding(mesh_a, P("data", None)))}}
        mgr.save(1, tree, blocking=True)

        mesh_b = auto_mesh((2, 4), ("data", "model"))
        sh = {{"w": NamedSharding(mesh_b, P("model", "data"))}}
        out = mgr.restore(tree, shardings=sh)
        np.testing.assert_array_equal(np.asarray(out["w"]),
                                      np.arange(64).reshape(8, 8))
        assert out["w"].sharding.spec == P("model", "data")
        print("elastic restore ok")
    """))


def test_trace_study_on_four_devices_matches_unsharded():
    """The user's multi-chip path (`Study.run(mesh=)`) on 4 virtual
    devices: the trace replay splits each block's streams over the mesh
    (small blocks, so several `lax.map` steps run) and the frame equals
    the unsharded one."""
    print(_run("""
        import jax, numpy as np
        from repro.api import Study, preset_grid
        from repro.api import simulator as sim
        from repro.core.workloads import Op

        sim._REPLAY_BLOCK_REQUESTS = 2 * 4096       # 2 streams per device
        mesh = auto_mesh((4,), ("data",), devices=jax.devices()[:4])
        grid = preset_grid(array=[16, 32, 64], sram_mb=[0.5, 2.0],
                           dataflow=["ws", "os"])
        ops = [Op("a", 256, 1024, 512), Op("b", 512, 197, 768, count=3.0),
               Op("v", kind="vector", vector_elems=8192.0)]
        mk = lambda: (Study().designs(grid).workloads({"w": ops})
                      .fidelity("fast", "trace"))
        plain = mk().run()
        shard = mk().run(mesh=mesh)
        assert not plain.failed_cells and not shard.failed_cells
        assert shard.fraction_batched == 1.0
        for k in ("total_cycles", "stall_cycles", "energy_pj", "edp",
                  "utilization"):
            np.testing.assert_allclose(shard[k], plain[k], rtol=1e-3)
        print("4-device trace study matches")
    """))


def test_importing_repro_initializes_no_backend():
    """A backend taken at import would hold the chip in a parent process
    (the farm smoke parent, a launcher) and starve its children."""
    print(_run("""
        import repro, repro.api, repro.farm
        from jax._src import xla_bridge
        assert not xla_bridge._backends, dict(xla_bridge._backends)
        print("no backend")
    """))
