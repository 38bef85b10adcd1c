"""Distributed-semantics tests (subprocess: each needs its own XLA
virtual-device count, which must be set before JAX initializes)."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str, devices: int = 8, timeout: int = 560):
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
        import sys; sys.path.insert(0, {repr(os.path.join(REPO, 'src'))})
    """) + textwrap.dedent(body)
    res = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=timeout)
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    return res.stdout


def test_ep_shard_map_matches_reference():
    """EP all_to_all dispatch == single-device routing (fwd, loss, grads)."""
    _run("""
        import jax, jax.numpy as jnp
        from repro.models import registry
        from repro.models.common import activation_sharding
        from repro.launch import shardings as shmod
        from repro.launch.mesh import make_mesh, mesh_context
        mesh = make_mesh((4, 2), ("data", "model"))
        arch = registry.get("deepseek-moe-16b").tiny()
        cfg, mod = arch.cfg, arch.module
        key = jax.random.PRNGKey(0)
        params = mod.init(cfg, key)
        toks = jax.random.randint(key, (8, 16), 0, 200)
        ref = mod.forward(cfg, params, toks)
        with mesh_context(mesh):
            with activation_sharding(shmod.activation_policy(mesh)):
                out = jax.jit(lambda p, t: mod.forward(cfg, p, t))(params, toks)
        err = float(jnp.max(jnp.abs(out - ref)))
        assert err < 5e-3, err
    """)


def test_sharded_train_step_matches_single_device():
    """The full production train step on a 2x2x2 mesh computes the same
    loss as the single-device step (same batch, same init)."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch import shardings as shmod, steps as steps_mod
        from repro.launch.mesh import make_mesh, mesh_context
        from repro.launch.shapes import ShapeSpec
        from repro.models import registry
        from repro.optim import adamw
        arch = registry.get("starcoder2-3b").tiny()
        cfg, mod = arch.cfg, arch.module
        key = jax.random.PRNGKey(0)
        params = mod.init(cfg, key)
        toks = jax.random.randint(key, (8, 32), 0, 200)
        batch = {"tokens": toks, "labels": toks}
        opt_cfg = adamw.AdamWConfig(lr=1e-3)
        opt_state = adamw.init_state(params)
        # single device
        fn1 = steps_mod.make_train_step(arch, opt_cfg, n_micro=1)
        p1, o1, m1 = jax.jit(fn1)(params, opt_state, batch)
        # 2x2x2 mesh with microbatching
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        scfg = shmod.ShardingConfig(fsdp=True)
        psh = shmod.param_shardings(jax.eval_shape(lambda: params), cfg, mesh, scfg)
        act = shmod.activation_policy(mesh)
        fn8 = steps_mod.make_train_step(arch, opt_cfg, n_micro=2,
                                        act_policy=act, mesh=mesh,
                                        grad_shardings=psh)
        with mesh_context(mesh):
            p8, o8, m8 = jax.jit(fn8, in_shardings=(psh, None, None),
                                 out_shardings=(psh, None, None))(
                params, opt_state, batch)
        assert abs(float(m1["loss"]) - float(m8["loss"])) < 5e-3, (
            float(m1["loss"]), float(m8["loss"]))
        for a, b in zip(jax.tree_util.tree_leaves(p1),
                        jax.tree_util.tree_leaves(p8)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32), atol=5e-2)
    """)


def test_dryrun_machinery_small_mesh():
    """lower_cell compiles a small train cell end-to-end on a 2x4 mesh and
    produces memory/cost/collective records."""
    _run("""
        import jax
        from repro.launch import dryrun
        from repro.launch.mesh import make_mesh
        from repro.launch.shapes import ShapeSpec
        mesh = make_mesh((2, 4), ("data", "model"))
        # seq must cover the VLM's 256 prefix-embedding tokens
        shape = ShapeSpec("train_tiny", seq=512, batch=8, kind="train")
        rec, compiled = dryrun.lower_cell("internvl2-2b", shape, mesh, n_micro=2)
        assert rec["hlo"]["flops_per_device"] > 0
        assert rec["memory"]["peak_per_device"] > 0
        assert rec["hlo"]["collective_counts"]
    """, devices=8)


def test_dryrun_import_leaves_xla_flags(monkeypatch):
    """Only ``dryrun.main`` asks for 512 virtual devices: importing the
    module must not reshape the backend of the process that imports it."""
    import importlib

    from repro.launch import dryrun
    monkeypatch.setenv("XLA_FLAGS", "--xla_dump_to=nowhere")
    importlib.reload(dryrun)
    assert os.environ["XLA_FLAGS"] == "--xla_dump_to=nowhere"


def test_collective_permute_and_groups_decode():
    """HLO analyzer's replica-group decoding on iota formats."""
    from repro.launch.hlo_analysis import decode_replica_groups
    g = decode_replica_groups("replica_groups=[32,16]<=[512]", 512)
    assert len(g) == 32 and len(g[0]) == 16 and g[0] == list(range(16))
    g = decode_replica_groups("replica_groups=[16,32]<=[32,16]T(1,0)", 512)
    assert len(g) == 16 and len(g[0]) == 32
    # transpose layout: group 0 collects one element from each 16-block
    assert g[0][:3] == [0, 16, 32]
    g = decode_replica_groups("replica_groups={{0,1},{2,3}}", 4)
    assert g == [[0, 1], [2, 3]]
