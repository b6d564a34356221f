"""The counting functions agree with the arrays' shapes at smoke sizes."""

import numpy as np
import pytest

from conftest import SMOKE_MODEL

MODEL = dict(SMOKE_MODEL, model_type='stablelm', partial_rotary_factor=0.25,
             rope_theta=10000, layer_norm_eps=1e-5, max_position_embeddings=4096,
             hidden_act='silu')


@pytest.fixture(scope='module')
def shapes():
    import jax
    from repro.configs import base
    from repro.core import yoco_linear
    from repro.models import model as model_mod
    cfg = base.ArchConfig(
        name='counts-test', family='dense', n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=2, d_ff=160, vocab_size=256,
        rope_fraction=0.25, norm_type='layernorm', max_seq_len=4096)
    p = jax.eval_shape(lambda: model_mod.init_params(jax.random.key(0), cfg))
    q = jax.eval_shape(yoco_linear.quantize_tree, p)
    return cfg, p, q


def test_matmul_shapes_are_the_weights(shapes):
    from chipbench.harness import counts
    cfg, p, _ = shapes
    L = MODEL['num_hidden_layers']
    calls = counts.matmul_calls(MODEL, 5, 5)
    assert len(calls) == 7 * L + 1
    want = []
    for _ in range(L):
        a, m = p['layers']['attn'], p['layers']['mlp']
        want += [a[n].shape[1:] for n in ('wq', 'wk', 'wv', 'wo')]
        want += [m[n].shape[1:] for n in ('w_gate', 'w_up', 'w_down')]
    want.append(p['lm_head'].shape)
    assert [(k, n) for _, k, n in calls] == [tuple(s) for s in want]


def test_int8_matmul_bytes_read_the_quantized_weights(shapes):
    """Over M token rows the int8 matmuls read M*K activations, the int8
    weights of the quantized tree once (all but the embedding) and write
    M*N int32 results."""
    import jax
    from chipbench.harness import counts
    _, _, q = shapes
    int8 = sum(x.size for x in jax.tree.leaves(q) if x.dtype == np.int8)
    m = 3
    calls = counts.matmul_calls(MODEL, m, m)
    ops, byt = counts.int8_matmul(calls)
    assert ops == sum(2 * m * k * n for _, k, n in calls)
    assert byt == int8 + sum(m * k + 4 * m * n for _, k, n in calls)


@pytest.mark.parametrize('kv_quant', [False, True])
def test_decode_attn_bytes_are_the_live_cache_rows(kv_quant):
    """Bytes of the live rows of a paged cache built by the program, at
    the leaves' own dtypes, split by the program's hotness rule."""
    import jax
    from repro.configs import base
    from repro.models import model as model_mod
    from chipbench.harness import counts
    cfg = base.ArchConfig(
        name='counts-test', family='dense', n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=2, d_ff=160, vocab_size=256,
        norm_type='layernorm')
    ps, hw = 8, 2
    cache = jax.eval_shape(lambda: model_mod.init_paged_cache_tree(
        cfg, 2, num_pages=20, page_size=ps, max_blocks=8,
        kv_dtype='int8' if kv_quant else None, hot_window=hw))['layers']
    row_fp = cache['k'].dtype.itemsize * np.prod(cache['k'].shape[3:])
    serving = dict(page_size=ps, hot_window=hw, kv_quant=kv_quant)
    h, dh = 4, 16
    for n in (1, 7, 8, 17, 40, 64):
        f, b = counts.decode_attn(MODEL, serving, [n])
        L = MODEL['num_hidden_layers']
        assert f == 4 * h * dh * n * L
        qo = 2 * h * dh * 4
        if not kv_quant:
            assert b == L * (2 * n * row_fp + qo)
            continue
        row_q8 = cache['kq'].dtype.itemsize * np.prod(cache['kq'].shape[3:])
        per_page_scales = 2 * cache['ks'].dtype.itemsize * cache['ks'].shape[-1]
        last = (n - 1) // ps
        cold_pages = [s for s in range(last + 1) if not s > last - hw]
        cold = len(cold_pages) * ps
        assert b == L * (2 * cold * row_q8 + len(cold_pages) * per_page_scales
                         + 2 * (n - cold) * row_fp + qo)


def test_model_flops_counts_prefill_and_decode():
    from chipbench.harness import counts
    d, v, L = 64, 256, 2
    per_tok = 2 * L * (64 * 64 * 2 + 64 * 32 * 2 + 64 * 160 * 3)
    att = 4 * 4 * 16 * L
    assert counts.model_flops(MODEL, [10], []) == per_tok + 2 * d * v + att * 10
    assert counts.model_flops(MODEL, [], [3]) == \
        3 * per_tok + 2 * d * v + att * 6


def test_unknown_device_is_an_error():
    from chipbench.harness import counts
    assert counts.peaks('TPU v5 lite')['int8_ops_per_s'] == 393e12
    with pytest.raises(KeyError):
        counts.peaks('TPU v9 imaginary')
