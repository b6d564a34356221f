"""Per-layer blocks for every assigned family: dense/MoE transformer blocks,
Mamba2 blocks, and the Zamba2 shared-attention block. Each block exposes
``init`` / ``apply`` (train & prefill) / ``decode`` with a uniform signature
so ``models.model`` can scan over homogeneous stacks.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.yoco_linear import YocoConfig
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import apply_mlp, apply_norm, dense_init, init_mlp, init_norm


# ----------------------------------------------------------------------------
# transformer block (dense or MoE mixer)
# ----------------------------------------------------------------------------
def init_transformer_block(key: jax.Array, cfg, *, use_moe: bool) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = dict(attn_norm=init_norm(cfg))
    p['attn'] = (attn_mod.init_mla(k1, cfg) if cfg.mla is not None
                 else attn_mod.init_attention(k1, cfg))
    p['mlp_norm'] = init_norm(cfg)
    if use_moe:
        p['moe'] = moe_mod.init_moe(k2, cfg)
    else:
        p['mlp'] = init_mlp(k3, cfg.d_model, cfg.d_ff, cfg.mlp_type)
    if getattr(cfg, 'sandwich_norm', False):
        p['post_attn_norm'] = init_norm(cfg)
        p['post_mlp_norm'] = init_norm(cfg)
    return p


def _mix_attn(p, x, cfg, yoco, *, window, theta, cache, cache_pos,
              decode_pos, rt=None, chunk_ctx=None, layer=None):
    if chunk_ctx is not None:
        if cfg.mla is not None:
            return attn_mod.mla_attention_chunk(
                p['attn'], x, cfg, yoco, cache=cache,
                offset=chunk_ctx['offset'], limit=chunk_ctx['limit'], rt=rt,
                layer=layer)
        return attn_mod.attention_chunk(
            p['attn'], x, cfg, yoco, cache=cache,
            offset=chunk_ctx['offset'], limit=chunk_ctx['limit'],
            window=window, theta=theta, rt=rt, layer=layer)
    if cfg.mla is not None:
        if decode_pos is not None:
            return attn_mod.mla_attention_decode(p['attn'], x, cfg, yoco,
                                                 cache=cache, pos=decode_pos,
                                                 rt=rt, layer=layer)
        return attn_mod.mla_attention(p['attn'], x, cfg, yoco, cache=cache,
                                      rt=rt, layer=layer)
    if decode_pos is not None:
        return attn_mod.attention_decode(p['attn'], x, cfg, yoco, cache=cache,
                                         pos=decode_pos, window=window,
                                         theta=theta, rt=rt, layer=layer)
    return attn_mod.attention(p['attn'], x, cfg, yoco, window=window,
                              theta=theta, cache=cache, cache_pos=cache_pos,
                              rt=rt, layer=layer)


def transformer_block(p: dict, x: jnp.ndarray, cfg, yoco: YocoConfig, *,
                      window=None, theta=None,
                      cache: Optional[dict] = None,
                      cache_pos=None, decode_pos=None,
                      moe_ctx=None, rt=None, chunk_ctx=None, layer=None
                      ) -> Tuple[jnp.ndarray, Optional[dict], dict]:
    """Pre-norm residual block. Returns (x, new_cache, metrics).
    ``chunk_ctx`` (dict(offset=, limit=), both (B,) int32) routes the
    attention mix through the chunked-prefill path instead. ``layer``
    (int32 scalar): ``cache`` is the whole layer-stacked cache node and
    this block reads and writes its layer ``layer`` in place."""
    h = apply_norm(p['attn_norm'], x, cfg)
    a, new_cache = _mix_attn(p, h, cfg, yoco, window=window, theta=theta,
                             cache=cache, cache_pos=cache_pos,
                             decode_pos=decode_pos, rt=rt,
                             chunk_ctx=chunk_ctx, layer=layer)
    if 'post_attn_norm' in p:
        a = apply_norm(p['post_attn_norm'], a, cfg)
    x = x + a
    h = apply_norm(p['mlp_norm'], x, cfg)
    metrics = {}
    if 'moe' in p:
        m, metrics = moe_mod.moe_apply(p['moe'], h, cfg, yoco, moe_ctx)
    else:
        m = apply_mlp(p['mlp'], h, cfg.mlp_type, yoco)
    if 'post_mlp_norm' in p:
        m = apply_norm(p['post_mlp_norm'], m, cfg)
    return x + m, new_cache, metrics


# ----------------------------------------------------------------------------
# mamba2 block
# ----------------------------------------------------------------------------
def init_mamba_block(key: jax.Array, cfg) -> dict:
    k1, k2 = jax.random.split(key)
    return dict(norm=init_norm(cfg), mixer=ssm_mod.init_mamba2(k1, cfg))


def mamba_block(p: dict, x: jnp.ndarray, cfg, yoco: YocoConfig, *,
                state: Optional[dict] = None, decode: bool = False,
                last_pos=None) -> Tuple[jnp.ndarray, Optional[dict]]:
    h = apply_norm(p['norm'], x, cfg)
    if decode:
        y, new_state = ssm_mod.mamba2_decode(p['mixer'], h, cfg, yoco,
                                             state=state)
    else:
        y, new_state = ssm_mod.mamba2_forward(p['mixer'], h, cfg, yoco,
                                              state=state,
                                              last_pos=last_pos)
    return x + y, new_state


# ----------------------------------------------------------------------------
# zamba2 shared block (one attn+MLP block applied at several sites)
# ----------------------------------------------------------------------------
def init_shared_block(key: jax.Array, cfg, n_sites: int) -> dict:
    """Shared transformer block + per-site input projections (the Zamba2
    pattern: block input is concat(hidden, original embedding) -> d)."""
    k1, k2, k3 = jax.random.split(key, 3)
    block = init_transformer_block(k1, cfg, use_moe=False)
    site_keys = jax.random.split(k2, n_sites)
    in_proj = jnp.stack([dense_init(k, 2 * cfg.d_model, cfg.d_model)
                         for k in site_keys])
    return dict(block=block, in_proj=in_proj)


def shared_block(p: dict, x: jnp.ndarray, x0: jnp.ndarray, site: int,
                 cfg, yoco: YocoConfig, *, cache=None, decode_pos=None,
                 rt=None) -> Tuple[jnp.ndarray, Optional[dict]]:
    """x0: the original embedding stream (concat-conditioning)."""
    h = jnp.concatenate([x, x0], axis=-1)
    h = jnp.einsum('bsd,df->bsf', h, p['in_proj'][site].astype(h.dtype))
    y, new_cache, _ = transformer_block(p['block'], h, cfg, yoco,
                                        cache=cache, decode_pos=decode_pos,
                                        rt=rt)
    return x + (y - h), new_cache     # residual on the block's own delta
