"""Optimizer: AdamW semantics, schedule, clipping, int8 EF compression."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim import adamw
from repro.distributed import collectives


def test_schedule_warmup_and_decay():
    cfg = adamw.OptConfig(lr=1e-3, warmup_steps=10, total_steps=100,
                          min_lr_ratio=0.1)
    assert float(adamw.schedule(jnp.float32(0), cfg)) == 0.0
    assert abs(float(adamw.schedule(jnp.float32(10), cfg)) - 1e-3) < 1e-9
    end = float(adamw.schedule(jnp.float32(100), cfg))
    assert abs(end - 1e-4) < 1e-8                 # min_lr_ratio * lr


def test_clip_by_global_norm():
    g = {'a': jnp.ones((10,)) * 10.0}
    clipped, gn = adamw.clip_by_global_norm(g, 1.0)
    assert abs(float(gn) - 10.0 * np.sqrt(10)) < 1e-3
    assert abs(float(adamw.global_norm(clipped)) - 1.0) < 1e-4


def test_update_moves_against_gradient():
    cfg = adamw.OptConfig(lr=0.1, warmup_steps=0, weight_decay=0.0,
                          total_steps=10)
    params = {'w': jnp.ones((4, 4))}
    state = adamw.init(params, cfg)
    grads = {'w': jnp.ones((4, 4))}
    new_params, state, m = adamw.update(params, grads, state, cfg)
    assert float(jnp.max(new_params['w'])) < 1.0


def test_weight_decay_only_on_matrices():
    cfg = adamw.OptConfig(lr=0.1, warmup_steps=0, weight_decay=1.0,
                          total_steps=10)
    params = {'w': jnp.ones((4, 4)), 'b': jnp.ones((4,))}
    state = adamw.init(params, cfg)
    grads = {'w': jnp.zeros((4, 4)), 'b': jnp.zeros((4,))}
    new_params, _, _ = adamw.update(params, grads, state, cfg)
    assert float(jnp.max(new_params['w'])) < 1.0   # decayed
    np.testing.assert_array_equal(np.asarray(new_params['b']), 1.0)


def test_ef_compression_unbiased_over_time():
    """Error feedback: the residual re-enters, so the *accumulated* update
    converges to the accumulated gradient."""
    g = jnp.array([1e-4, 1.0, -0.5, 3e-5])        # tiny grads get crushed
    ef = jnp.zeros_like(g)
    total_wire = jnp.zeros_like(g)
    for _ in range(64):
        wire, ef = adamw.compress_decompress(g, ef)
        total_wire += wire
    np.testing.assert_allclose(np.asarray(total_wire / 64), np.asarray(g),
                               atol=1e-4)


def test_compressed_psum_on_single_device_mesh():
    mesh = jax.make_mesh((1,), ('d',))
    x = jnp.array([0.1, -2.0, 3.0])
    ef = jnp.zeros_like(x)

    def f(x, ef):
        return collectives.compressed_psum(x, 'd', ef)

    mean, new_ef = jax.shard_map(
        f, mesh=mesh,
        in_specs=(jax.sharding.PartitionSpec(), jax.sharding.PartitionSpec()),
        out_specs=(jax.sharding.PartitionSpec(), jax.sharding.PartitionSpec()),
        check_vma=False)(x, ef)
    np.testing.assert_allclose(np.asarray(mean), np.asarray(x), atol=0.03)
    # residual + dequantized == original
    np.testing.assert_allclose(np.asarray(mean + new_ef), np.asarray(x),
                               atol=1e-6)


def test_grad_accum_equivalence():
    """A=2 microbatches must equal one full batch (linear loss in batch)."""
    from repro import configs
    from repro.data import synthetic
    from repro.models import model as M
    from repro.runtime import train_step as TS

    cfg = configs.get('stablelm-1.6b', smoke=True)
    params = M.init_params(jax.random.key(0), cfg)
    dc = synthetic.for_arch(cfg, global_batch=4, seq_len=16)
    batch = synthetic.make_batch(dc, 0)

    o1 = adamw.OptConfig(lr=1e-2, warmup_steps=0, total_steps=10,
                         grad_accum=1, clip_norm=1e9)
    o2 = adamw.OptConfig(lr=1e-2, warmup_steps=0, total_steps=10,
                         grad_accum=2, clip_norm=1e9)
    p1, _, m1 = TS.make_train_step(cfg, opt_cfg=o1)(
        params, adamw.init(params, o1), batch)
    p2, _, m2 = TS.make_train_step(cfg, opt_cfg=o2)(
        params, adamw.init(params, o2), batch)
    # losses per microbatch differ, but the mean gradient is the same batch
    # mean => parameter updates agree up to bf16 forward rounding (params
    # are cast to bf16 on-shard before the model — §Perf iteration 3)
    d = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                  - b.astype(jnp.float32))))
            for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
    assert d < 2e-2, d
