"""The reference draws the program's weights from the seed by its own
code, and computes what the program computes."""

import numpy as np
import pytest

from conftest import SMOKE_MODEL


@pytest.fixture(scope='module')
def pair():
    import jax
    from repro.configs import base
    from repro.models import model as model_mod
    from chipbench.harness import arch
    c = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
             num_key_value_heads=2, intermediate_size=160, vocab_size=256,
             partial_rotary_factor=0.25, rope_theta=10000,
             layer_norm_eps=1e-5, max_position_embeddings=4096)
    assert all(c[k] == v for k, v in SMOKE_MODEL.items())
    cfg = base.ArchConfig(
        name='ref-test', family='dense', n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=160, vocab_size=256, rope_fraction=0.25,
        norm_type='layernorm', max_seq_len=4096)
    seed = 123456789
    ref = arch.module('stablelm')
    return c, cfg, seed, model_mod.init_params(jax.random.key(seed), cfg), ref


def test_weights_bitwise_equal_to_program_init(pair):
    import jax
    c, cfg, seed, p, ref = pair
    np.testing.assert_array_equal(ref.embedding(seed, c), p['embed'])
    np.testing.assert_array_equal(ref.lm_head(seed, c), p['lm_head'])
    for i, lk in enumerate(ref.layer_keys(seed, c['num_hidden_layers'])):
        w = ref.layer_weights(lk, c)
        for name in ('wq', 'wk', 'wv', 'wo'):
            np.testing.assert_array_equal(w[name], p['layers']['attn'][name][i])
        for name in ('w_gate', 'w_up', 'w_down'):
            np.testing.assert_array_equal(w[name], p['layers']['mlp'][name][i])
    for leaf in jax.tree.leaves(p['layers']['attn_norm']['scale']):
        np.testing.assert_array_equal(leaf, 1.0)


def test_reference_matches_program_forward_in_f32(pair):
    """The program's own training forward, run with float32 compute,
    agrees with the reference: the program's best token reads a gap of 0
    and its worst the program's whole logit range, at every position."""
    import jax
    import jax.numpy as jnp
    from repro.core.yoco_linear import YocoConfig
    from repro.models import model as model_mod
    from repro.models.model import ModelRuntime
    c, cfg, seed, p, ref = pair
    toks = np.random.default_rng(0).integers(0, 256, (1, 40)).astype(np.int32)
    with jax.default_matmul_precision('highest'):
        want, _ = model_mod.forward(
            p, dict(inputs=jnp.asarray(toks)), cfg,
            YocoConfig(compute_dtype=jnp.float32),
            ModelRuntime(compute_dtype=jnp.float32))
    h = ref.hidden(seed, c, toks)
    want = np.asarray(want[0])
    pos = np.arange(toks.shape[1])
    served, _ = ref.head_gaps(seed, c, h, pos, np.argmax(want, -1))
    np.testing.assert_allclose(served, 0.0, atol=2e-4)
    wrong = np.argmin(want, -1)
    served, _ = ref.head_gaps(seed, c, h, pos, wrong)
    np.testing.assert_allclose(served, want.max(-1) - want.min(-1),
                               rtol=1e-4, atol=2e-4)
