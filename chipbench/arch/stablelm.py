"""StableLM 2 (``model_type`` ``stablelm``): the program's configuration,
the plain float32 reference and the counts, as ``harness/arch.py`` asks.

The reference imports nothing of the program under test. Its weights come
from the seed by the initialisation scheme the configuration file states
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
"""

from __future__ import annotations

import functools
from typing import Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.harness import reference as ref
from chipbench.harness.reference import HI, Q_BLOCK, _mm, _normal


def arch_config(conf: dict):
    """The program's ``ArchConfig`` for a configuration file (the file is
    the configuration as run)."""
    from repro.configs import base
    c = conf['model']
    return base.ArchConfig(
        name=conf['name'], family='dense',
        n_layers=c['num_hidden_layers'], d_model=c['hidden_size'],
        n_heads=c['num_attention_heads'],
        n_kv_heads=c['num_key_value_heads'], d_ff=c['intermediate_size'],
        vocab_size=c['vocab_size'], rope_theta=float(c['rope_theta']),
        rope_fraction=float(c['partial_rotary_factor']),
        mlp_type={'silu': 'swiglu'}[c['hidden_act']],
        norm_type='layernorm', max_seq_len=c['max_position_embeddings'],
        source=conf['source'])


def dims(c: dict) -> dict:
    d = c['hidden_size']
    h = c['num_attention_heads']
    return dict(d=d, h=h, hkv=c['num_key_value_heads'], dh=d // h,
                ff=c['intermediate_size'], v=c['vocab_size'],
                rot=int((d // h) * c['partial_rotary_factor']) // 2 * 2,
                theta=float(c['rope_theta']), eps=float(c['layer_norm_eps']),
                L=c['num_hidden_layers'])


# ----------------------------------------------------------------------------
# the reference
# ----------------------------------------------------------------------------
def _split_root(seed):
    """Root split: (embedding, layers, head) keys, as the file states.
    ``seed`` is an int or a uint32 array (the same key either way)."""
    k_emb, k_layers, k_head, _, _ = jax.random.split(jax.random.key(seed), 5)
    return k_emb, k_layers, k_head


def layer_keys(seed, n_layers: int):
    return jax.random.split(_split_root(seed)[1], n_layers)


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
    LayerNorm and LM head in float32, and the gaps of ``toks`` and of the
    control's first choices over ``hc`` (``reference.logit_gaps``)."""
    c = dict(cj)
    w = lm_head(seed_arr, c)
    eps = float(c['layer_norm_eps'])
    lg = _mm(_layernorm(h[idx], eps), w, None)
    lgc = None if control is None else \
        _mm(_layernorm(hc[idx], eps), w, control)
    return ref.logit_gaps(lg, toks, lgc)


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
    """Gaps at the positions ``pos`` of one sequence's hidden states:
    (served gaps, control gaps)."""
    head = functools.partial(_head_gaps, jnp.asarray(seed, jnp.uint32),
                             cj=_freeze(c), control=control)
    return ref.row_blocks(head, h, pos, toks, hc)


# ----------------------------------------------------------------------------
# the counts
# ----------------------------------------------------------------------------
def layer_matmuls(c: dict) -> List[Tuple[str, int, int]]:
    """(name, K, N) of every projection one token passes in one layer."""
    m = dims(c)
    d, h, hkv, dh, ff = m['d'], m['h'], m['hkv'], m['dh'], m['ff']
    return [('wq', d, h * dh), ('wk', d, hkv * dh), ('wv', d, hkv * dh),
            ('wo', h * dh, d), ('w_gate', d, ff), ('w_up', d, ff),
            ('w_down', ff, d)]


def matmul_calls(c: dict, m: int, head_rows: int) -> List[Tuple[int, int, int]]:
    """(M, K, N) of every matmul one program call makes over ``m`` token
    rows: each layer's projections, then the LM head over ``head_rows``
    rows (every row when decoding, the last prompt row in a prefill)."""
    calls = [(m, k, n) for _ in range(c['num_hidden_layers'])
             for _, k, n in layer_matmuls(c)]
    calls.append((head_rows, c['hidden_size'], c['vocab_size']))
    return calls


def decode_attn(c: dict, serving: dict, ctx: Iterable[int]) -> Tuple[float, float]:
    """(flops, bytes) of one decode step's attention over the live
    context of each lane, summed over layers. A lane at context ``n``
    attends positions 0..n-1; with the int8 tier the pages older than the
    hot window are read as int8 plus one f32 scale per page and head for
    K and for V, the rest as bf16. Queries in and outputs out are f32."""
    m = dims(c)
    h, hkv, dh = m['h'], m['hkv'], m['dh']
    ps = int(serving['page_size'])
    hw = int(serving['hot_window']) if serving.get('kv_quant') else None
    flops = byt = 0.0
    for n in ctx:
        flops += 4.0 * h * dh * n
        if hw is None:
            byt += n * hkv * dh * 2 * 2
        else:
            last = (n - 1) // ps                 # block being written
            cold_pages = max(0, last - hw + 1)
            cold = min(n, cold_pages * ps)
            byt += cold * hkv * dh * 2 * 1 + cold_pages * hkv * 2 * 4
            byt += (n - cold) * hkv * dh * 2 * 2
        byt += 2 * h * dh * 4
    return flops * m['L'], byt * m['L']


def model_flops(c: dict, ctx: Iterable[int], prefills: Iterable[int]) -> float:
    """Model FLOPs of a set of decode lanes (live contexts) and prompt
    prefills (actual lengths): 2 per weight per token through the
    projections, the LM head once per decoded token and once per prompt,
    and causal attention over the live context."""
    m = dims(c)
    per_tok = 2.0 * sum(k * n for _, k, n in layer_matmuls(c)) * m['L']
    head = 2.0 * m['d'] * m['v']
    att = 4.0 * m['h'] * m['dh'] * m['L']
    total = 0.0
    for n in ctx:
        total += per_tok + head + att * n
    for p in prefills:
        total += per_tok * p + head + att * p * (p + 1) / 2.0
    return total
