"""End-to-end behaviour tests: the training driver learns, survives a
restart bit-exactly, and the tiered optimizer trains equivalently."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.pipeline import DataConfig, TokenPipeline
from repro.models import registry
from repro.optim import adamw, offload, schedules


def _tiny_setup(arch_id="starcoder2-3b", seed=0, batch=4, seq=32):
    arch = registry.get(arch_id).tiny()
    cfg, mod = arch.cfg, arch.module
    params = mod.init(cfg, jax.random.PRNGKey(seed))
    data = TokenPipeline(DataConfig(vocab=cfg.vocab_padded, batch=batch,
                                    seq=seq, seed=11))
    return cfg, mod, params, data


@pytest.mark.slow
def test_training_reduces_loss():
    cfg, mod, params, data = _tiny_setup()
    opt_cfg = adamw.AdamWConfig(lr=3e-3, schedule=schedules.constant(),
                                weight_decay=0.01)
    state = adamw.init_state(params)

    @jax.jit
    def step(params, state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: mod.loss(cfg, p, batch))(params)
        params, state, m = adamw.apply(opt_cfg, params, grads, state)
        return params, state, loss

    losses = []
    for s in range(60):
        batch = {k: jnp.asarray(v) for k, v in data.batch_at(s).items()}
        params, state, loss = step(params, state, batch)
        losses.append(float(loss))
    assert np.mean(losses[-10:]) < np.mean(losses[:10]) - 0.3, losses[::10]


@pytest.mark.slow
def test_training_restart_is_bit_exact(tmp_path):
    """Kill at step 12, restore the step-10 checkpoint, finish at 20:
    identical params to the uninterrupted run (deterministic pipeline)."""
    from repro.checkpoint.checkpointer import Checkpointer
    cfg, mod, params0, data = _tiny_setup()
    opt_cfg = adamw.AdamWConfig(lr=1e-3, schedule=schedules.constant())

    @jax.jit
    def step(params, state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: mod.loss(cfg, p, batch))(params)
        params, state, _ = adamw.apply(opt_cfg, params, grads, state)
        return params, state

    def run(n_steps, ckpt=None, resume=False):
        params, state = params0, adamw.init_state(params0)
        start = 0
        if resume:
            start, (params, state), _ = ckpt.restore((params, state))
        for s in range(start, n_steps):
            batch = {k: jnp.asarray(v) for k, v in data.batch_at(s).items()}
            params, state = step(params, state, batch)
            if ckpt and (s + 1) % 10 == 0:
                ckpt.save(s + 1, (params, state))
                ckpt.wait()
        return params

    clean = run(20)
    ck = Checkpointer(str(tmp_path), asynchronous=False)
    run(12, ckpt=ck)  # "crashes" after step 12; last checkpoint at 10
    recovered = run(20, ckpt=ck, resume=True)
    for p1, p2 in zip(jax.tree_util.tree_leaves(clean),
                      jax.tree_util.tree_leaves(recovered)):
        np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))


def test_tiered_optimizer_training_equivalence():
    """Training with host-offloaded moments tracks the fused optimizer."""
    cfg, mod, params, data = _tiny_setup(seed=1)
    opt_cfg = adamw.AdamWConfig(lr=1e-3, schedule=schedules.constant())
    pf, sf = params, adamw.init_state(params)
    opt = offload.TieredAdamW(opt_cfg, slow_fraction=1.0, min_offload_bytes=1024)
    pt, st = params, opt.init(params)
    loss_grad = jax.jit(jax.value_and_grad(lambda p, b: mod.loss(cfg, p, b)))
    for s in range(3):
        batch = {k: jnp.asarray(v) for k, v in data.batch_at(s).items()}
        _, gf = loss_grad(pf, batch)
        pf, sf, _ = adamw.apply(opt_cfg, pf, gf, sf)
        _, gt = loss_grad(pt, batch)
        pt, st, _ = opt.step(pt, gt, st)
    for a, b in zip(jax.tree_util.tree_leaves(pf),
                    jax.tree_util.tree_leaves(pt)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=5e-5)


@pytest.fixture
def cache_dir_from_env(monkeypatch, tmp_path):
    """main() turns on the persistent compile cache; with the variable set
    it leaves JAX's configuration, and this worker, as they are."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


@pytest.mark.usefixtures("cache_dir_from_env")
def test_train_driver_main_runs():
    from repro.launch import train as train_mod
    losses = train_mod.main([
        "--arch", "internvl2-2b", "--tiny", "--steps", "12", "--batch", "2",
        "--seq", "16", "--ckpt-every", "100", "--log-every", "6",
        "--offload-fraction", "0.0",
    ])
    assert len(losses) == 12 and np.isfinite(losses).all()


@pytest.mark.usefixtures("cache_dir_from_env")
def test_serve_driver_main_runs():
    from repro.launch import serve as serve_mod
    done = serve_mod.main([
        "--arch", "internvl2-2b", "--tiny", "--requests", "4",
        "--max-batch", "2", "--max-len", "32", "--new-tokens", "4",
        "--slow-fraction", "0.5", "--page-t", "8",
    ])
    assert len(done) == 4
