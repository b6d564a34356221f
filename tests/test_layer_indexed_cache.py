"""The layer scan carries the stacked paged pools and indexes them by
layer in place (``models/model.py::_transformer_stack``).

Two contracts, both on the CPU in interpret mode:

* every flash-decode entrypoint given ``layer=l`` over a layer-stacked
  pool returns exactly what it returns over ``pool[l]`` (the kernel DMAs
  layer ``l``'s pages straight out of the stack);
* ``decode_step`` and ``prefill_step`` return the logits and cache leaves
  of a plain per-layer loop that slices each layer's cache, runs
  ``blocks.transformer_block`` on it and restacks the results — for the
  paged, paged-q8, contiguous and MLA paged layouts, and gemma's
  per-layer windows. The loop and the scan compile to differently fused
  programs, so they run in float32 and agree to the kernels' parity
  tolerance (``test_layouts.py``: 2e-5), not bit for bit;
* rows stored padded to whole lanes, as on a TPU, serve the same logits.

The compiled-program side (no op of a step program copies a pool) is in
``tests/test_tpu_compile.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.yoco_linear import YocoConfig
from repro.kernels import flash_decode as fd
from repro.models import blocks as blk
from repro.models import model as M
from repro.models.layers import apply_norm
from repro.models.model import ModelRuntime
from repro.runtime import kv_cache as kvc
from repro.runtime import kv_quant as kvq
from repro.runtime import serve_step as SS

NL, B, W, PS = 3, 2, 4, 8          # layers, slots, table width, page size
NP = 1 + B * W                     # pool pages incl. the garbage page
POS = jnp.asarray([13, 29], jnp.int32)
F32 = jnp.float32
YOCO = YocoConfig(compute_dtype=F32)
ATOL = 2e-5                        # a kernel vs its own layout's oracle
BT = jnp.asarray(np.random.RandomState(0).permutation(
    np.arange(1, NP)).reshape(B, W).astype(np.int32))


def _normal(i, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.key(i), shape, dtype)


def _q8_pools(hkv, dh):
    """Layer-stacked fp + int8 GQA pools with per-page scales, each
    layer's cold pages already quantized."""
    shape = (NL, NP, PS, hkv, dh)
    one = dict(k=_normal(1, shape), v=_normal(2, shape),
               kq=jnp.zeros(shape, jnp.int8), vq=jnp.zeros(shape, jnp.int8),
               ks=jnp.zeros((NL, NP, hkv)), vs=jnp.zeros((NL, NP, hkv)))
    pages = jnp.asarray(kvq.cold_page_list(BT, POS, PS, 1), jnp.int32)
    layers = [kvq.quantize_pages_layer(
        {k: a[l] for k, a in one.items()}, pages) for l in range(NL)]
    return {k: jnp.stack([c[k] for c in layers]) for k in one}


def _mla_q8_pools(dk):
    shape = (NL, NP, PS, dk)
    layers = []
    for l in range(NL):
        c = dict(cl=_normal(10 + l, shape[1:]),
                 clq=jnp.zeros(shape[1:], jnp.int8),
                 cs=jnp.zeros((NP, 1)))
        layers.append(kvq.quantize_latent_pages_layer(
            c, jnp.asarray(kvq.cold_page_list(BT, POS, PS, 1), jnp.int32)))
    return {k: jnp.stack([c[k] for c in layers]) for k in layers[0]}


def _entry(name):
    """(call(pools, layer) -> output, stacked pools) for one entrypoint;
    ``layer=None`` calls it on a single layer's pools."""
    hkv, g, dh, r, dr, h = 2, 2, 16, 24, 8, 4
    scale = 0.25
    q = _normal(0, (B, 1, hkv * g, dh))
    q_mla = _normal(5, (B, 1, h, r + dr))
    q_chunk = _normal(6, (1, PS, hkv * g, dh))
    q_chunk_mla = _normal(7, (1, PS, h, r + dr))
    kw = dict(scale=scale, interpret=True)
    off, lim = jnp.asarray([8], jnp.int32), jnp.asarray([14], jnp.int32)
    kv = {k: _normal(i, (NL, NP, PS, hkv, dh))
          for i, k in ((1, 'k'), (2, 'v'))}
    if name == 'paged':
        return (lambda c, l: fd.flash_decode_paged(
            q, c['k'], c['v'], POS, BT, layer=l, **kw)), kv
    if name == 'paged_windowed':
        return (lambda c, l: fd.flash_decode_paged(
            q, c['k'], c['v'], POS, BT, window=jnp.asarray([5, 20]),
            layer=l, **kw)), kv
    if name == 'paged_q8':
        return (lambda c, l: fd.flash_decode_paged_q8(
            q, c['k'], c['v'], c['kq'], c['vq'], c['ks'], c['vs'], POS,
            BT, jnp.asarray([1]), layer=l, **kw)), _q8_pools(hkv, dh)
    if name == 'chunk':
        return (lambda c, l: fd.flash_chunk_paged(
            q_chunk, c['k'], c['v'], off, lim, BT[:1], layer=l, **kw)), kv
    if name in ('contiguous', 'contiguous_padded'):
        s = 256 if name == 'contiguous' else 40
        dense = {k: _normal(i, (NL, B, s, hkv, dh))
                 for i, k in ((3, 'k'), (4, 'v'))}
        return (lambda c, l: fd.flash_decode(
            q, c['k'], c['v'], POS, layer=l, **kw)), dense
    if name == 'mla':
        return (lambda c, l: fd.flash_decode_paged_mla(
            q_mla, c['cl'], POS, BT, r=r, layer=l, **kw)), dict(
                cl=_normal(8, (NL, NP, PS, r + dr)))
    if name == 'mla_q8':
        return (lambda c, l: fd.flash_decode_paged_mla_q8(
            q_mla, c['cl'], c['clq'], c['cs'], POS, BT, jnp.asarray([1]),
            r=r, layer=l, **kw)), _mla_q8_pools(r + dr)
    if name == 'mla_chunk':
        return (lambda c, l: fd.flash_chunk_paged_mla(
            q_chunk_mla, c['cl'], off, lim, BT[:1], r=r, layer=l, **kw)), \
            dict(cl=_normal(9, (NL, NP, PS, r + dr)))
    raise KeyError(name)


@pytest.mark.parametrize('layer', range(NL))
@pytest.mark.parametrize('name', ['paged', 'paged_windowed', 'paged_q8',
                                  'chunk', 'contiguous',
                                  'contiguous_padded', 'mla', 'mla_q8',
                                  'mla_chunk'])
def test_entrypoint_layer_operand_reads_that_layer(name, layer):
    """``layer=l`` over the stacked buffer == the same call on ``[l]``,
    bit for bit (the same kernel body over the same pages)."""
    call, stacked = _entry(name)
    got = call(stacked, jnp.int32(layer))
    want = call({k: a[layer] for k, a in stacked.items()}, None)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ----------------------------------------------------------------------------
# whole step programs vs a per-layer loop over sliced caches
# ----------------------------------------------------------------------------
def _reference(params, x, cache, cfg, rt, *, decode_pos=None,
               last_pos=None):
    """The step as one block per layer over that layer's sliced cache,
    restacked afterwards; returns (last-position logits, cache)."""
    if cfg.moe is not None and cfg.moe.first_k_dense:
        groups = [('dense_prefix', 'prefix'), ('layers', 'moe')]
    else:
        groups = [('layers', 'layers')]
    gemma = cfg.local_global_every > 0
    if gemma:
        window, theta = M._gemma_layer_meta(cfg)
    new = {}
    for pkey, ckey in groups:
        stack, lc = params[pkey], cache[ckey]
        outs = []
        for l in range(jax.tree.leaves(stack)[0].shape[0]):
            h_cache = jax.tree.map(lambda a: a[l], lc)
            x, h_cache, _ = blk.transformer_block(
                jax.tree.map(lambda a: a[l], stack), x, cfg, YOCO,
                window=window[l] if gemma else cfg.sliding_window,
                theta=theta[l] if gemma else None, cache=h_cache,
                decode_pos=decode_pos, rt=rt)
            outs.append(h_cache)
        new[ckey] = jax.tree.map(lambda *a: jnp.stack(a), *outs)
    if decode_pos is None:
        x = jnp.take_along_axis(x, last_pos.reshape(-1, 1, 1), axis=1)
    x = apply_norm(params['final_norm'], x, cfg)
    return M._head(params, x, cfg, YOCO)[:, 0], new


def _assert_close(got, want):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g, F32), np.asarray(w, F32),
                                   rtol=ATOL, atol=ATOL)


CASES = {
    'paged': ('stablelm-1.6b', None),
    'paged_q8': ('stablelm-1.6b', 'int8'),
    'contiguous': ('stablelm-1.6b', 'contiguous'),
    'mla_paged': ('deepseek-v3-671b', None),
    'gemma_paged': ('gemma3-27b', None),
}


def _setup(case):
    arch, kind = CASES[case]
    cfg = configs.get(arch, smoke=True)
    rt = ModelRuntime(attn_impl='flash', compute_dtype=F32)
    params = M.init_params(jax.random.key(0), cfg)
    plen = 12
    toks = jax.random.randint(jax.random.key(1), (B, plen), 0,
                              cfg.vocab_size)
    lens = jnp.asarray([plen, plen - 5], jnp.int32)
    if kind == 'contiguous':
        cache = M.init_cache_tree(cfg, B, 128, F32)
        lens = jnp.full((B,), plen, jnp.int32)
    else:
        cache = M.init_paged_cache_tree(cfg, B, num_pages=NP, page_size=PS,
                                        max_blocks=W, dtype=F32,
                                        kv_dtype=kind, hot_window=1)
        cache = kvc.with_block_tables(cache, BT)
    return cfg, rt, params, toks, lens, cache, kind


def _quantize_aged(cache, kind, lens):
    if kind != 'int8':
        return cache
    return kvq.quantize_tree_pages(cache, jnp.asarray(
        kvq.cold_page_list(BT, lens + 8, PS, 1), jnp.int32))


@pytest.mark.parametrize('case', list(CASES))
def test_steps_match_per_layer_loop(case):
    """prefill_step then two decode_steps, each against the per-layer
    reference from the same input cache: equal logits and equal cache
    leaves (flash kernels in interpret mode)."""
    cfg, rt, params, toks, lens, cache, kind = _setup(case)
    prefill = jax.jit(SS.make_prefill_step(cfg, YOCO, rt))
    ref_prefill = jax.jit(lambda p, t, c, lp: _reference(
        p, M._embed(p, dict(inputs=t), cfg, rt), c, cfg, rt, last_pos=lp))
    got = prefill(params, dict(inputs=toks), cache, lens - 1)
    _assert_close(got, ref_prefill(params, toks, cache, lens - 1))
    cache = _quantize_aged(got[1], kind, lens)

    decode = jax.jit(SS.make_decode_step(cfg, YOCO, rt))
    ref_decode = jax.jit(lambda p, t, pos, c: _reference(
        p, M._embed(p, dict(inputs=t[:, None]), cfg, rt), c, cfg, rt,
        decode_pos=pos))
    tok = jnp.asarray([3, 5], jnp.int32)
    for step in range(2):
        pos = lens + step
        nxt, logits, new = decode(params, tok, pos, cache)
        _assert_close((logits, new), ref_decode(params, tok, pos, cache))
        tok, cache = nxt, new


def _serve_steps(case):
    """Logits and cache after prefill_step and two decode_steps."""
    cfg, rt, params, toks, lens, cache, kind = _setup(case)
    logits, cache = jax.jit(SS.make_prefill_step(cfg, YOCO, rt))(
        params, dict(inputs=toks), cache, lens - 1)
    cache = _quantize_aged(cache, kind, lens)
    out = [logits]
    decode = jax.jit(SS.make_decode_step(cfg, YOCO, rt))
    tok = jnp.asarray([3, 5], jnp.int32)
    for step in range(2):
        tok, logits, cache = decode(params, tok, lens + step, cache)
        out.append(logits)
    return out, cache


@pytest.mark.parametrize('case', list(CASES))
def test_rows_padded_to_lanes_serve_the_same(case, monkeypatch):
    """On a TPU every K/V and latent row is stored padded to 128 lanes
    (``kv_cache.row_width``). With that padding forced on the CPU the
    steps give the unpadded steps' logits, the pools hold the unpadded
    rows in their first lanes, and the padding lanes stay zero."""
    want_logits, want = _serve_steps(case)
    monkeypatch.setattr(kvc, '_lanes', lambda: kvc.LANES)
    got_logits, got = _serve_steps(case)
    _assert_close(got_logits, want_logits)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        if g.shape == w.shape:
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
            continue
        assert g.shape[:-1] == w.shape[:-1]
        assert g.shape[-1] % kvc.LANES == 0 and g.shape[-1] > w.shape[-1]
        d = w.shape[-1]
        np.testing.assert_allclose(np.asarray(g[..., :d], F32),
                                   np.asarray(w, F32), rtol=ATOL, atol=ATOL)
        assert not np.any(np.asarray(g[..., d:]))
