"""Training runtime: convergence, grad-accum equivalence, preemption
checkpointing, straggler watchdog, gradient compression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import OptimizerConfig, TrainConfig, get_smoke_config
from repro.runtime.fault_tolerance import StepWatchdog
from repro.runtime.grad_compress import (compress_gradients,
                                         dp_int8_allreduce, residuals)
from repro.runtime.train_loop import Trainer, make_train_step
from repro.optim.adamw import init_opt_state
from repro.launch.io import make_batch


def test_loss_decreases(tmp_path):
    cfg = get_smoke_config("llama3-8b").replace(remat=False)
    tc = TrainConfig(model=cfg, seq_len=24, global_batch=8, steps=60,
                     optimizer=OptimizerConfig(lr=1e-2, warmup_steps=3,
                                               decay_steps=60),
                     checkpoint_dir=str(tmp_path), checkpoint_every=1000,
                     log_every=59)
    out = Trainer(tc).run()
    first, last = out["log"][0]["loss"], out["log"][-1]["loss"]
    # the synthetic language is 45% copy-task (slow induction learning);
    # the markov share alone gives a reliable drop by step 60 (measured
    # trajectory: 5.57 -> 4.6)
    assert last < first - 0.5, (first, last)


def test_grad_accum_equivalence():
    """accum=2 over a batch == accum=1 over the same batch (up to fp)."""
    cfg = get_smoke_config("olmo-1b").replace(
        remat=False, dtype="float32", param_dtype="float32")
    api_batch = make_batch(cfg, 4, 16)
    step1 = make_train_step(cfg, OptimizerConfig(), grad_accum=1)
    step2 = make_train_step(cfg, OptimizerConfig(), grad_accum=2)
    from repro.models import get_model
    params = get_model(cfg).init_params(jax.random.PRNGKey(0), cfg)
    opt = init_opt_state(params, OptimizerConfig())
    p1, _, m1 = step1(params, opt, api_batch)
    p2, _, m2 = step2(params, opt, api_batch)
    # microbatch losses average to ~the same value; params should agree
    for a, b in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=5e-3)


def test_preemption_triggers_checkpoint(tmp_path):
    cfg = get_smoke_config("olmo-1b").replace(remat=False)
    tc = TrainConfig(model=cfg, seq_len=16, global_batch=4, steps=50,
                     optimizer=OptimizerConfig(lr=1e-3),
                     checkpoint_dir=str(tmp_path), checkpoint_every=1000,
                     log_every=1, async_checkpoint=False)
    tr = Trainer(tc)
    tr.preemption.trigger()                      # simulate SIGTERM
    out = tr.run()
    assert out["step"] == 1                       # stopped at first step
    assert tr.ckpt.latest_step() == 1             # but saved its state


def test_watchdog_flags_stragglers():
    wd = StepWatchdog(threshold=2.0, warmup_steps=1)
    for i in range(5):
        wd.record(i, 1.0)
    assert wd.record(5, 5.0) is True
    assert wd.stragglers[-1][0] == 5
    assert wd.record(6, 1.0) is False            # EMA not poisoned
    assert abs(wd.ema - 1.0) < 0.05


def test_grad_compression_roundtrip_error():
    g = {"w": jax.random.normal(jax.random.PRNGKey(0), (1000,))}
    out = compress_gradients(g)
    err = jnp.max(jnp.abs(out["w"] - g["w"]))
    bound = jnp.max(jnp.abs(g["w"])) / 127.0
    assert float(err) <= float(bound) + 1e-6


def test_grad_compression_error_feedback():
    g = {"w": jax.random.normal(jax.random.PRNGKey(1), (512,))}
    r = residuals(g)
    # one more round with error feedback reduces bias: E[g + e] closer to g
    out = compress_gradients(g, error_feedback=r)
    plain = compress_gradients(g)
    err_fb = float(jnp.mean(jnp.abs(out["w"] - g["w"] - r["w"])))
    assert err_fb <= float(jnp.max(jnp.abs(g["w"]))) / 127.0 + 1e-6
    assert not bool(jnp.any(jnp.isnan(out["w"])))
    del plain


def test_dp_int8_allreduce_single_device():
    """On a 1-device mesh the compressed all-reduce reduces to the identity
    quant/dequant round."""
    from repro.launch.mesh import make_mesh
    from repro.sharding.api import shard_map_unchecked
    from jax.sharding import PartitionSpec as P
    mesh = make_mesh((1,), ("data",))
    g = {"w": jax.random.normal(jax.random.PRNGKey(2), (64, 8))}

    def f(g):
        return dp_int8_allreduce(g, "data")

    out = jax.jit(shard_map_unchecked(f, mesh, (P(),), P()))(g)
    err = jnp.max(jnp.abs(out["w"] - g["w"]))
    assert float(err) <= float(jnp.max(jnp.abs(g["w"]))) / 127.0 + 1e-6
