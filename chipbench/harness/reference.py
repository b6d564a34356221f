"""Plain float32 reference of the served transformer, in ``jax.numpy``.

It imports nothing of the program under test. Its weights come from the
seed by the initialisation scheme the configuration file states
(``init``: normal weights at ``1/sqrt(fan_in)``, embedding at 0.02,
LayerNorm at scale 1 and bias 0, drawn from the threefry stream in the
order written below), so it takes no array the program made.

The forward is the configuration's mathematics with nothing fused: token
embedding; per layer a pre-LayerNorm (eps from the file), q/k/v
projections, rotary embedding on the first ``partial_rotary_factor`` of
each head (rotate-half), causal softmax attention with grouped KV heads,
the output projection and residual; a pre-LayerNorm SwiGLU MLP and
residual; the final LayerNorm and the LM head. Every matmul runs at
``Precision.HIGHEST``.

Memory: weights are drawn one layer at a time inside the layer's jitted
call, attention runs in query blocks, and the LM head in row blocks, so a
12B-wide layer stack fits on one chip once the program has let go of it.
Every shape is fixed by the traffic's longest sequence and the row block,
so each program compiles once and then comes from the persistent cache.

``control`` names a lower precision for every projection's operands
(weights per output channel, activations per token): ``fp8`` (e4m3) or
``int4`` (symmetric, levels -7..7). The control reading of a cell is the
gap, in the float32 reference's logits, of the token the control puts
first.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
ROW_BLOCK = 512


def _normal(key, shape, fan_in: Optional[int] = None, std: float = 0.0):
    x = jax.random.normal(key, shape, jnp.float32)
    if fan_in is not None:
        return x * (1.0 / jnp.sqrt(fan_in))
    return x * std


def _split_root(seed):
    """Root split: (embedding, layers, head) keys, as the file states.
    ``seed`` is an int or a uint32 array (the same key either way)."""
    k_emb, k_layers, k_head, _, _ = jax.random.split(jax.random.key(seed), 5)
    return k_emb, k_layers, k_head


def layer_keys(seed, n_layers: int):
    return jax.random.split(_split_root(seed)[1], n_layers)


def dims(c: dict) -> dict:
    d = c['hidden_size']
    h = c['num_attention_heads']
    return dict(d=d, h=h, hkv=c['num_key_value_heads'], dh=d // h,
                ff=c['intermediate_size'], v=c['vocab_size'],
                rot=int((d // h) * c['partial_rotary_factor']) // 2 * 2,
                theta=float(c['rope_theta']), eps=float(c['layer_norm_eps']),
                L=c['num_hidden_layers'])


def layer_weights(lkey, c: dict) -> dict:
    """One layer's weights from its key: block key -> (attn, mlp) keys."""
    m = dims(c)
    d, h, hkv, dh, ff = m['d'], m['h'], m['hkv'], m['dh'], m['ff']
    k_attn, _, k_mlp, _ = jax.random.split(lkey, 4)
    kq, kk, kv, ko = jax.random.split(k_attn, 4)
    kg, ku, kd = jax.random.split(k_mlp, 3)
    return dict(
        wq=_normal(kq, (d, h * dh), d), wk=_normal(kk, (d, hkv * dh), d),
        wv=_normal(kv, (d, hkv * dh), d), wo=_normal(ko, (h * dh, d), h * dh),
        w_gate=_normal(kg, (d, ff), d), w_up=_normal(ku, (d, ff), d),
        w_down=_normal(kd, (ff, d), ff))


def embedding(seed, c: dict):
    m = dims(c)
    return _normal(_split_root(seed)[0], (m['v'], m['d']), std=0.02)


def lm_head(seed, c: dict):
    m = dims(c)
    return _normal(_split_root(seed)[2], (m['d'], m['v']), m['d'])


# ----------------------------------------------------------------------------
# lower-precision operands for the control
# ----------------------------------------------------------------------------
def _lowp(x, axis: int, control: Optional[str]):
    """Round ``x`` to the control's precision, scaled per slice along
    every axis but ``axis`` (absmax to the format's largest value)."""
    if control is None:
        return x
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    if control == 'fp8':
        s = jnp.maximum(amax, 1e-30) / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    if control == 'int4':
        s = jnp.maximum(amax, 1e-30) / 7.0
        return jnp.clip(jnp.round(x / s), -7, 7) * s
    raise ValueError(f'unknown control precision {control!r}')


def _mm(x, w, control):
    """(..., K) @ (K, N) in float32, operands at the control's precision
    (activations per token, weights per output channel)."""
    return jnp.matmul(_lowp(x, -1, control), _lowp(w, 0, control),
                      precision=HI)


def _layernorm(x, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)     # scale 1, bias 0


def _rope(x, m):
    """Rotate-half rotary on the first ``rot`` dims; positions 0..S-1."""
    rot = m['rot']
    s = x.shape[1]
    inv = 1.0 / (m['theta'] ** (jnp.arange(0, rot, 2, dtype=jnp.float32)
                                / rot))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv   # (S, rot/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def _attention(q, k, v, m):
    """Causal attention, query rows in blocks. q (B,S,H,dh); k/v
    (B,S,Hkv,dh)."""
    b, s, h, dh = q.shape
    g = h // m['hkv']
    q = q.reshape(b, s, m['hkv'], g, dh)
    kpos = jnp.arange(s)
    outs = []
    for lo in range(0, s, Q_BLOCK):
        qb = q[:, lo:lo + Q_BLOCK]
        lg = jnp.einsum('bqkgd,bskd->bkgqs', qb, k, precision=HI) \
            / jnp.sqrt(jnp.float32(dh))
        qpos = lo + jnp.arange(qb.shape[1])
        lg = jnp.where(kpos[None, :] <= qpos[:, None], lg, -jnp.inf)
        p = jax.nn.softmax(lg, axis=-1)
        outs.append(jnp.einsum('bkgqs,bskd->bqkgd', p, v, precision=HI))
    return jnp.concatenate(outs, axis=1).reshape(b, s, h * dh)


@functools.partial(jax.jit, static_argnames=('cj', 'control'))
def _layer(lkey, x, *, cj, control):
    c = dict(cj)
    m = dims(c)
    w = layer_weights(lkey, c)
    b, s, _ = x.shape
    hn = _layernorm(x, m['eps'])
    q = _mm(hn, w['wq'], control).reshape(b, s, m['h'], m['dh'])
    k = _mm(hn, w['wk'], control).reshape(b, s, m['hkv'], m['dh'])
    v = _mm(hn, w['wv'], control).reshape(b, s, m['hkv'], m['dh'])
    a = _attention(_rope(q, m), _rope(k, m), v, m)
    x = x + _mm(a, w['wo'], control)
    hn = _layernorm(x, m['eps'])
    mlp = jax.nn.silu(_mm(hn, w['w_gate'], control)) * _mm(hn, w['w_up'],
                                                           control)
    return x + _mm(mlp, w['w_down'], control)


@functools.partial(jax.jit, static_argnames=('cj',))
def _embed(seed_arr, tokens, *, cj):
    return jnp.take(embedding(seed_arr, dict(cj)), tokens, axis=0)


@functools.partial(jax.jit, static_argnames=('cj',))
def _layer_keys(seed_arr, *, cj):
    return layer_keys(seed_arr, dict(cj)['num_hidden_layers'])


@functools.partial(jax.jit, static_argnames=('cj', 'control'))
def _head_gaps(seed_arr, h, idx, toks, hc, *, cj, control):
    """For the rows ``idx`` of the hidden states ``h`` (S, d): the final
    LayerNorm and LM head in float32, and the gap by which ``toks`` lie
    below each row's best logit. With ``control``, also the float32 gap
    of the token the control precision's head over ``hc`` puts first."""
    c = dict(cj)
    w = lm_head(seed_arr, c)
    eps = float(c['layer_norm_eps'])
    lg = _mm(_layernorm(h[idx], eps), w, None)
    best = jnp.max(lg, axis=-1)
    served = best - jnp.take_along_axis(lg, toks[:, None], axis=-1)[:, 0]
    if control is None:
        return served, served
    pick = jnp.argmax(_mm(_layernorm(hc[idx], eps), w, control), axis=-1)
    return served, best - jnp.take_along_axis(lg, pick[:, None],
                                              axis=-1)[:, 0]


def _freeze(c: dict):
    keys = ('hidden_size', 'num_attention_heads', 'num_key_value_heads',
            'intermediate_size', 'vocab_size', 'partial_rotary_factor',
            'rope_theta', 'layer_norm_eps', 'num_hidden_layers')
    return tuple((k, c[k]) for k in keys)


def hidden(seed: int, c: dict, tokens: np.ndarray,
           control: Optional[str] = None):
    """(1, S) int tokens -> (S, d) float32 hidden states before the final
    norm."""
    cj = _freeze(c)
    seed_arr = jnp.asarray(seed, jnp.uint32)
    with jax.default_matmul_precision('highest'):
        x = _embed(seed_arr, jnp.asarray(tokens, jnp.int32), cj=cj)
        for lk in _layer_keys(seed_arr, cj=cj):
            x = _layer(lk, x, cj=cj, control=control)
    return x[0]


def head_gaps(seed: int, c: dict, h, pos: np.ndarray, toks: np.ndarray,
              hc=None, control: Optional[str] = None):
    """Gaps at the positions ``pos`` of one sequence's hidden states, in
    blocks of ``ROW_BLOCK`` rows (the last block padded by repeating its
    last row, and cut back): (served gaps, control gaps)."""
    cj = _freeze(c)
    seed_arr = jnp.asarray(seed, jnp.uint32)
    n = len(pos)
    pad = -n % ROW_BLOCK
    pos = np.concatenate([pos, np.full(pad, pos[-1])]).astype(np.int32)
    toks = np.concatenate([toks, np.full(pad, toks[-1])]).astype(np.int32)
    out_s, out_c = [], []
    with jax.default_matmul_precision('highest'):
        for lo in range(0, len(pos), ROW_BLOCK):
            sl = slice(lo, lo + ROW_BLOCK)
            g, gc = _head_gaps(seed_arr, h, jnp.asarray(pos[sl]),
                               jnp.asarray(toks[sl]),
                               h if hc is None else hc, cj=cj,
                               control=control)
            out_s.append(np.asarray(g))
            out_c.append(np.asarray(gc))
    return np.concatenate(out_s)[:n], np.concatenate(out_c)[:n]
