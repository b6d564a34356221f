"""API-drift canary tests.

The code is written for the installed JAX (0.9.0). These tests import
every module under ``src/repro`` and run a tiny forward in each of the
four YocoConfig modes, so the next JAX API drift fails loudly at
import/smoke level instead of deep inside a parametrized kernel test.
"""

import importlib
import pkgutil

import jax
import jax.numpy as jnp
import pytest

import repro


def _all_repro_modules():
    mods = []
    for info in pkgutil.walk_packages(repro.__path__, prefix='repro.'):
        mods.append(info.name)
    return sorted(mods)


@pytest.mark.parametrize('mod', _all_repro_modules())
def test_every_module_imports(mod):
    importlib.import_module(mod)


@pytest.mark.parametrize('mode', ['bf16', 'qat', 'w8a8', 'analog_sim'])
def test_tiny_forward_every_yoco_mode(mode):
    """One small train-style forward per execution mode — the smoke canary
    that exercises quant/analog/kernel dispatch end to end."""
    from repro import configs
    from repro.core.yoco_linear import YocoConfig
    from repro.models import model as M

    cfg = configs.get('stablelm-1.6b', smoke=True)
    params = M.init_params(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 8), 0, cfg.vocab_size)
    logits, _ = M.forward(params, dict(inputs=toks), cfg,
                          YocoConfig(mode=mode))
    assert logits.shape == (2, 8, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))


@pytest.mark.parametrize('mode', ['bf16', 'w8a8'])
def test_tiny_decode_every_yoco_mode(mode):
    """Prefill + one batched-pos decode step per serving-relevant mode."""
    from repro import configs
    from repro.core.yoco_linear import YocoConfig
    from repro.models import model as M

    cfg = configs.get('stablelm-1.6b', smoke=True)
    yoco = YocoConfig(mode=mode)
    params = M.init_params(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 8), 0, cfg.vocab_size)
    cache = M.init_cache_tree(cfg, 2, 12)
    logits, cache = M.prefill(params, dict(inputs=toks), cache, cfg, yoco,
                              last_pos=jnp.array([7, 5], jnp.int32))
    assert logits.shape == (2, cfg.vocab_size)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    pos = jnp.array([8, 6], jnp.int32)
    logits2, _ = M.decode_step(params, tok, pos, cache, cfg, yoco)
    assert logits2.shape == (2, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits2.astype(jnp.float32))))
