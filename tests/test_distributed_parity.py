"""Multi-(virtual-)device parity: the distributed execution paths — TP
layout, fsdp2d 2-D layout (sequence-sharded activations + shard_map MLA
latent core), and EP MoE all_to_all — must compute the same loss as the
single-device reference.

Everything runs in-process: tests/conftest.py forces a multi-device host
platform (``--xla_force_host_platform_device_count``, set before
``import jax``), so the grid below runs REAL collectives on real device
shards of a 2x2 mesh whose axes are typed ``Auto`` (the sharding mode the
model code is written for)."""

import dataclasses
import functools

import numpy as np
import pytest

pytestmark = pytest.mark.slow


@pytest.mark.distributed
def test_host_platform_is_multidevice():
    """conftest.py forced the multi-device CPU host platform before jax
    import — the precondition for every in-process distributed test."""
    import jax
    assert jax.default_backend() == 'cpu'
    assert jax.device_count() >= 4, jax.devices()


@pytest.mark.distributed
def test_in_process_shard_map_psum():
    """A real psum across 4 forced host devices, in-process."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:4]), ('data',))
    f = jax.shard_map(lambda x: jax.lax.psum(x, 'data'), mesh=mesh,
                      in_specs=P('data'), out_specs=P())
    x = jnp.arange(8.0)
    got = np.asarray(jax.jit(f)(x))
    want = np.asarray(x).reshape(4, 2).sum(axis=0)
    np.testing.assert_allclose(got, want)


def _rel_err(a, b):
    import jax.numpy as jnp
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-9))


@functools.lru_cache(maxsize=None)
def _parity_errors(arch, ep):
    """{layout: rel err} of the distributed losses against the single-
    device reference for one arch, on a 2x2 Auto-typed mesh of the forced
    host devices (one model init + reference loss per arch, shared by its
    two layout cases)."""
    import jax
    from jax.sharding import AxisType
    from repro import configs
    from repro.core.yoco_linear import YocoConfig
    from repro.data import synthetic
    from repro.distributed import sharding
    from repro.models import model as M
    from repro.models.model import ModelRuntime

    cfg = configs.get(arch, smoke=True)
    if ep and cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, impl='ep',
                                         capacity_factor=100.0))
    params = M.init_params(jax.random.key(0), cfg)
    batch = synthetic.make_batch(
        synthetic.for_arch(cfg, global_batch=4, seq_len=32), 0)
    # single-device reference (dense MoE oracle)
    cfg_ref = cfg
    if ep and cfg.moe is not None:
        cfg_ref = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, impl='dense'))
    yoco = YocoConfig(mode='bf16')
    ref, _ = M.loss_fn(params, batch, cfg_ref, yoco)

    mesh = jax.make_mesh((2, 2), ('data', 'model'),
                         axis_types=(AxisType.Auto,) * 2)
    errs = {}
    for layout in ('tp', 'fsdp2d'):
        rt = ModelRuntime(mesh=mesh, dp_axes=('data',), use_ep=ep,
                          act_layout='2d' if layout == 'fsdp2d' else 'batch')
        params_d = jax.device_put(params, sharding.to_shardings(
            mesh, sharding.param_specs(params, mesh, layout)))
        batch_d = jax.device_put(batch, sharding.to_shardings(
            mesh, sharding.batch_specs(cfg, ('data',))))
        with jax.set_mesh(mesh):
            loss, _ = jax.jit(
                lambda p, b: M.loss_fn(p, b, cfg, yoco, rt))(params_d,
                                                            batch_d)
        errs[layout] = _rel_err(loss, ref)
    return errs


_ARCHS = dict(dense=('stablelm-1.6b', False),
              mla_moe=('deepseek-v3-671b', True),
              gqa_moe=('qwen2-moe-a2.7b', True),
              ssm=('mamba2-780m', False))


@pytest.mark.distributed
@pytest.mark.parametrize('name', [
    'dense.tp', 'dense.fsdp2d',
    'mla_moe.tp', 'mla_moe.fsdp2d',     # fsdp2d exercises the shard_map
    'gqa_moe.tp', 'gqa_moe.fsdp2d',     # MLA latent core + EP all_to_all
    'ssm.tp', 'ssm.fsdp2d',
])
def test_distributed_loss_matches_reference(name):
    family, layout = name.split('.')
    err = _parity_errors(*_ARCHS[family])[layout]
    # bf16 forward + resharding reassociation tolerance
    assert err < 0.02, (name, err)
