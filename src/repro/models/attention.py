"""Attention: GQA (with KV cache, sliding window, qk-norm, biases) and
DeepSeek MLA (multi-head latent attention) with the *absorbed* decode path.

All projections route through ``core.yoco_linear`` so the paper's 8-bit
execution modes apply. The softmax/AV contraction itself stays bf16/f32 —
the paper quantizes VMMs against *stored* weights; dynamic QK^T products
carry >8b dynamic range and are exactly the "no mid-reduction rounding"
boundary (PAPER.md, Eq. 3/4 discussion).

Decode runs either through the einsum ``_sdpa`` oracle (default) or the
fused Pallas flash-decode kernel (``rt.attn_impl == 'flash'``, see
``kernels/flash_decode.py``), which never materializes the (B, S_max)
logits. Both accept a per-request ``pos`` vector so one jit'd step serves
requests at heterogeneous positions.

Cache layouts
-------------
Cache dicts are classified by ``runtime.layouts``'s :class:`CacheLayout`
registry — the ONE place allowed to inspect cache leaves. This module
asks the registry for the layout once per call and goes through its write
ops / densify oracle / kernel entrypoint; it never tests leaf names
itself. The six layouts and their leaf schemas (contiguous GQA/MLA, paged
GQA/MLA, and the two int8-tiered paged layouts) are documented in
``runtime/layouts.py``; :func:`init_paged_cache` below builds the paged
ones. The MLA latent tier (``kv_dtype='int8'`` on an MLA config)
quantizes cold ``cl`` pages per-page absmax *before* the W_uk/W_uv
expansion — its own error model, validated in tests/test_layouts.py
against the tier-mixing absorbed einsum oracle.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import yoco_linear
from repro.core.yoco_linear import YocoConfig
from repro.models import rope as rope_mod
from repro.models.layers import dense_init, rmsnorm

NEG_INF = -2.0e38


# ----------------------------------------------------------------------------
# init
# ----------------------------------------------------------------------------
def init_attention(key: jax.Array, cfg) -> dict:
    """Standard GQA projection weights (optionally biased / qk-normed)."""
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = dict(
        wq=dense_init(k1, d, h * dh),
        wk=dense_init(k2, d, hkv * dh),
        wv=dense_init(k3, d, hkv * dh),
        wo=dense_init(k4, h * dh, d),
    )
    if cfg.attn_bias:
        p['bq'] = jnp.zeros((h * dh,), jnp.float32)
        p['bk'] = jnp.zeros((hkv * dh,), jnp.float32)
        p['bv'] = jnp.zeros((hkv * dh,), jnp.float32)
    if cfg.qk_norm:
        p['q_norm'] = jnp.zeros((dh,), jnp.float32)
        p['k_norm'] = jnp.zeros((dh,), jnp.float32)
    return p


def init_mla(key: jax.Array, cfg) -> dict:
    m, d, h = cfg.mla, cfg.d_model, cfg.n_heads
    ks = jax.random.split(key, 6)
    return dict(
        w_dq=dense_init(ks[0], d, m.q_lora_rank),
        w_uq=dense_init(ks[1], m.q_lora_rank,
                        h * (m.nope_head_dim + m.rope_head_dim)),
        w_dkv=dense_init(ks[2], d, m.kv_lora_rank + m.rope_head_dim),
        w_ukv=dense_init(ks[3], m.kv_lora_rank,
                         h * (m.nope_head_dim + m.v_head_dim)),
        wo=dense_init(ks[4], h * m.v_head_dim, d),
        q_ln=jnp.zeros((m.q_lora_rank,), jnp.float32),
        kv_ln=jnp.zeros((m.kv_lora_rank,), jnp.float32),
    )


def init_cache(cfg, batch: int, max_seq: int, dtype=jnp.bfloat16,
               n_sites: int = 0) -> dict:
    """Empty KV cache. ``n_sites`` > 0 prepends a site dim (zamba2 shared
    blocks: one cache per application site). GQA rows are stored
    ``kv_cache.row_width`` wide, as in :func:`init_paged_cache`."""
    lead = (n_sites,) if n_sites else ()
    if cfg.mla is not None:
        m = cfg.mla
        return dict(
            ckv=jnp.zeros(lead + (batch, max_seq, m.kv_lora_rank), dtype),
            krope=jnp.zeros(lead + (batch, max_seq, m.rope_head_dim), dtype),
        )
    from repro.runtime.kv_cache import row_width
    hkv, dh = cfg.n_kv_heads, row_width(cfg.resolved_head_dim)
    return dict(
        k=jnp.zeros(lead + (batch, max_seq, hkv, dh), dtype),
        v=jnp.zeros(lead + (batch, max_seq, hkv, dh), dtype),
    )


def init_paged_cache(cfg, batch: int, *, num_pages: int, page_size: int,
                     max_blocks: int, dtype=jnp.bfloat16,
                     kv_dtype: Optional[str] = None,
                     hot_window: int = 1) -> dict:
    """Empty paged KV cache: one physical pool (page 0 = garbage page) plus
    all-garbage block tables. ``runtime.kv_cache.PagedKVCache`` owns the
    allocation state; this is just the device arrays.

    ``kv_dtype='int8'`` adds the hybrid-precision tier (``runtime.kv_quant``
    contract): int8 cold pools + per-page/per-head scales + the
    ``hot_window`` knob (in pages, >= 1; >= max_blocks disables the int8
    tier). ``dtype`` stays the hot/fp tier's dtype.

    MLA configs get the latent layout instead: one ``cl`` pool of width
    ``r + d_rope`` per layer (same block tables). Their int8 tier
    (``runtime.layouts.PagedMLAQ8Layout``) quantizes cold latent pages
    with ONE per-page absmax scale — the rounding happens *before* the
    W_uk/W_uv expansion, a different error model from the GQA tier (see
    ``runtime/kv_quant.py``).

    Each K/V or latent row is stored ``kv_cache.row_width`` wide: padded
    with zero lanes to a multiple of 128 on a TPU, so the pools keep the
    row-major layout the flash kernels read."""
    from repro.runtime.kv_cache import row_width
    if kv_dtype not in (None, 'fp', 'int8'):
        raise ValueError(f'kv_dtype must be None/"fp"/"int8", got {kv_dtype!r}')
    tiered = kv_dtype == 'int8'
    if tiered and hot_window < 1:
        raise ValueError('hot_window must be >= 1: the page being written '
                         'is always full-precision')
    if cfg.mla is not None:
        m = cfg.mla
        dk = row_width(m.kv_lora_rank + m.rope_head_dim)
        cache = dict(
            cl=jnp.zeros((num_pages, page_size, dk), dtype),
            bt=jnp.zeros((batch, max_blocks), jnp.int32),
        )
        if tiered:
            cache.update(
                clq=jnp.zeros((num_pages, page_size, dk), jnp.int8),
                cs=jnp.zeros((num_pages, 1), jnp.float32),
                hw=jnp.full((1,), hot_window, jnp.int32),
            )
        return cache
    hkv, dh = cfg.n_kv_heads, row_width(cfg.resolved_head_dim)
    cache = dict(
        k=jnp.zeros((num_pages, page_size, hkv, dh), dtype),
        v=jnp.zeros((num_pages, page_size, hkv, dh), dtype),
        bt=jnp.zeros((batch, max_blocks), jnp.int32),
    )
    if tiered:
        cache.update(
            kq=jnp.zeros((num_pages, page_size, hkv, dh), jnp.int8),
            vq=jnp.zeros((num_pages, page_size, hkv, dh), jnp.int8),
            ks=jnp.zeros((num_pages, hkv), jnp.float32),
            vs=jnp.zeros((num_pages, hkv), jnp.float32),
            hw=jnp.full((1,), hot_window, jnp.int32),
        )
    return cache


# ----------------------------------------------------------------------------
# masks
# ----------------------------------------------------------------------------
def causal_mask(sq: int, skv: int, offset: int = 0,
                window: Optional[int] = None) -> jnp.ndarray:
    """(sq, skv) additive mask. ``offset`` = absolute position of query 0
    minus position of key 0. ``window``: sliding-window width (keys within
    [pos_q - window + 1, pos_q])."""
    qpos = jnp.arange(sq)[:, None] + offset
    kpos = jnp.arange(skv)[None, :]
    ok = kpos <= qpos
    if window is not None:
        ok &= kpos > qpos - window
    return jnp.where(ok, 0.0, NEG_INF)


def decode_mask(pos: jnp.ndarray, smax: int,
                window=None) -> jnp.ndarray:
    """Length mask for single-token decode against a (.., S_max, ..) cache.

    ``pos`` scalar -> (1, smax) (broadcasts over every batch/head dim);
    ``pos`` (B,)   -> (B, smax) — callers insert their own head/query dims
    (the GQA and MLA logit layouts differ in rank)."""
    kpos = jnp.arange(smax)
    if jnp.ndim(pos) == 0:
        ok = kpos <= pos
        if window is not None:
            ok &= kpos > pos - window
        return jnp.where(ok, 0.0, NEG_INF)[None, :]
    p = pos[:, None]
    ok = kpos[None, :] <= p
    if window is not None:
        w = jnp.asarray(window)
        w = w[:, None] if w.ndim else w
        ok &= kpos[None, :] > p - w
    return jnp.where(ok, 0.0, NEG_INF)


def chunk_mask(offset, c: int, smax: int, window=None) -> jnp.ndarray:
    """(B, C, smax) additive causal mask for a prefill chunk whose C query
    rows sit at absolute positions offset[b] .. offset[b] + C - 1 against
    a length-``smax`` densified cache view. Rows past the prompt length
    mask like real rows (their outputs are finite garbage the caller
    discards)."""
    qp = (jnp.asarray(offset, jnp.int32).reshape(-1, 1, 1)
          + jnp.arange(c, dtype=jnp.int32)[None, :, None])
    kpos = jnp.arange(smax, dtype=jnp.int32)[None, None, :]
    ok = kpos <= qp
    if window is not None:
        ok &= kpos > qp - window
    return jnp.where(ok, 0.0, NEG_INF)


def _cache_update(c: jnp.ndarray, t: jnp.ndarray, pos) -> jnp.ndarray:
    """Write the step's K/V slab ``t`` (B, 1, ...) into a contiguous cache
    ``c`` (B, S_max, ...) at absolute position ``pos`` (scalar, or (B,)
    for heterogeneous-position batches). Thin alias of the registry's
    dense write op (the layouts own all cache-writing discipline)."""
    from repro.runtime import layouts
    return layouts.dense_token_update(c, t, pos)


# ----------------------------------------------------------------------------
# core attention math (pure, shared by all paths)
# ----------------------------------------------------------------------------
def _sdpa(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
          mask: Optional[jnp.ndarray], scale: float) -> jnp.ndarray:
    """q: (B, Sq, H, dh); k/v: (B, Skv, Hkv, dh) with H % Hkv == 0.

    Operands stay bf16 with f32 MXU accumulation (preferred_element_type);
    only the softmax runs in f32. Keeping q/k/v bf16 halves every
    sequence-parallel K/V gather on the wire (see ROADMAP.md) at identical
    accumulation precision. ``mask`` broadcasts against the (b, hkv, g, q, s)
    logits: (q, s)/(1, s) for shared masks, (b, 1, 1, 1, s) per-request."""
    b, sq, h, dh = q.shape
    hkv = k.shape[2]
    g = h // hkv
    q = q.reshape(b, sq, hkv, g, dh)
    logits = jnp.einsum('bqkgd,bskd->bkgqs', q, k,
                        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        logits = logits + mask                      # (sq, skv) broadcasts
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum('bkgqs,bskd->bqkgd', probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, sq, h, dh).astype(v.dtype)


def sdpa_decode(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, pos,
                scale: float, window=None) -> jnp.ndarray:
    """Single-token decode attention via the einsum path: the reference
    oracle the flash-decode kernel is validated against (tests and
    benchmarks call this exact function, not a re-assembled copy).

    q: (B, 1, H, dh); k/v: (B, S_max, Hkv, dh); pos scalar or (B,)."""
    mask = decode_mask(pos, k.shape[1], window)
    if jnp.ndim(pos) != 0:
        mask = mask[:, None, None, None, :]
    return _sdpa(q, k, v, mask, scale)


# ----------------------------------------------------------------------------
# GQA forward (train / prefill / decode)
# ----------------------------------------------------------------------------
def _tp_heads_gather(out_flat: jnp.ndarray, rt) -> jnp.ndarray:
    """The ONE collective of the TP serving path: inside a ``shard_map``
    body (``rt.tp_reduce`` = the mesh axis name) every rank holds the
    attention outputs of its own contiguous head slice; a tiled all-gather
    on the flattened head dim reassembles the full head-major (B, S, H*dh)
    activation BEFORE the replicated ``wo`` projection. Concatenating
    independent per-head outputs is bit-exact vs the single-device run —
    unlike a psum over partial ``wo`` products, which would reassociate the
    float reduction. Outside shard_map (``tp_reduce`` unset): identity."""
    if rt is not None and getattr(rt, 'tp_reduce', None):
        return jax.lax.all_gather(out_flat, rt.tp_reduce, axis=out_flat.ndim - 1,
                                  tiled=True)
    return out_flat


def _project_qkv(p: dict, x: jnp.ndarray, cfg, yoco: YocoConfig,
                 positions: jnp.ndarray, theta: float):
    b, s, _ = x.shape
    dh = cfg.resolved_head_dim
    q = yoco_linear.linear(x, p['wq'], p.get('bq'), cfg=yoco)
    k = yoco_linear.linear(x, p['wk'], p.get('bk'), cfg=yoco)
    v = yoco_linear.linear(x, p['wv'], p.get('bv'), cfg=yoco)
    # head counts derive from the projection widths, not cfg: inside a TP
    # shard_map body each rank sees only its own contiguous head slice
    q = q.reshape(b, s, -1, dh)
    k = k.reshape(b, s, -1, dh)
    v = v.reshape(b, s, -1, dh)
    if cfg.qk_norm:
        q = rmsnorm(q, p['q_norm'])
        k = rmsnorm(k, p['k_norm'])
    if cfg.mrope:
        if positions.ndim == 2:
            positions = jnp.stack([positions] * 3, axis=-1)
        q = rope_mod.apply_mrope(q, positions, theta)
        k = rope_mod.apply_mrope(k, positions, theta)
    else:
        q = rope_mod.apply_rope(q, positions, theta, cfg.rope_fraction)
        k = rope_mod.apply_rope(k, positions, theta, cfg.rope_fraction)
    return q, k, v


def attention(p: dict, x: jnp.ndarray, cfg, yoco: YocoConfig, *,
              positions: Optional[jnp.ndarray] = None,
              window: Optional[int] = None,
              theta: Optional[float] = None,
              cache: Optional[dict] = None,
              cache_pos: Optional[jnp.ndarray] = None,
              rt=None, layer=None,
              ) -> Tuple[jnp.ndarray, Optional[dict]]:
    """Full-sequence attention (train) or prefill (``cache`` given: KV written
    at [0, s)). Returns (out, updated_cache). ``layer`` (int32 scalar):
    ``cache`` is the layer-stacked node, written at that layer in place
    (the same contract on every decode/chunk entry point below)."""
    b, s, _ = x.shape
    dh = cfg.resolved_head_dim
    theta = theta if theta is not None else cfg.rope_theta
    if positions is None:
        positions = rope_mod.default_positions(b, s)
    q, k, v = _project_qkv(p, x, cfg, yoco, positions, theta)
    new_cache = None
    if cache is not None:
        from repro.runtime import layouts
        # quantized layouts prefill the fp (hot) pools too — the scheduler
        # quantizes aged-out pages after admission; tier leaves pass
        # through untouched (the layout owns that discipline)
        new_cache = layouts.get_layout(cache).write_prefill(
            cache, dict(k=k, v=v), layer=layer)
    mask = causal_mask(s, s, 0, window)
    out = _sdpa(q, k, v, mask, 1.0 / jnp.sqrt(dh).astype(jnp.float32))
    out = _tp_heads_gather(out.reshape(b, s, -1), rt)
    out = yoco_linear.linear(out, p['wo'], cfg=yoco)
    return out, new_cache


def attention_decode(p: dict, x: jnp.ndarray, cfg, yoco: YocoConfig, *,
                     cache: dict, pos: jnp.ndarray,
                     window: Optional[int] = None,
                     theta: Optional[float] = None,
                     rt=None, layer=None,
                     ) -> Tuple[jnp.ndarray, dict]:
    """One-token decode. x: (B, 1, d); ``pos``: scalar int or (B,) vector of
    per-request absolute positions being generated; cache holds [0, pos)
    valid entries per request.

    ``rt.attn_impl == 'flash'`` routes the cache read through the fused
    Pallas flash-decode kernel (online softmax, no (B, S_max) logits in
    HBM); the default einsum ``_sdpa`` is the reference oracle."""
    b = x.shape[0]
    dh = cfg.resolved_head_dim
    theta = theta if theta is not None else cfg.rope_theta
    if jnp.ndim(pos) == 0:
        positions = jnp.full((b, 1), pos, jnp.int32)
    else:
        positions = jnp.asarray(pos, jnp.int32).reshape(b, 1)
    q, k, v = _project_qkv(p, x, cfg, yoco, positions, theta)
    scale = 1.0 / float(dh) ** 0.5
    use_flash = (rt is not None
                 and getattr(rt, 'attn_impl', 'einsum') == 'flash')
    from repro.runtime import layouts
    layout = layouts.get_layout(cache)
    posr = (jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
            if layout.paged else pos)
    # writes always land in the fp (hot-tier) pools, quantized or not
    new_cache = layout.write_token(cache, dict(k=k, v=v), posr, layer=layer)
    if use_flash:
        out = layout.flash_decode(q, new_cache, posr, scale=scale,
                                  window=window, layer=layer)
    else:
        # einsum oracle on the layout's densified (tier-mixing) view
        kd, vd = layout.gather(new_cache, posr, layer=layer, width=dh)
        out = sdpa_decode(q, kd, vd, posr, scale, window)
    out = _tp_heads_gather(out.reshape(b, 1, -1), rt)
    out = yoco_linear.linear(out, p['wo'], cfg=yoco)
    return out, new_cache


def attention_chunk(p: dict, x: jnp.ndarray, cfg, yoco: YocoConfig, *,
                    cache: dict, offset: jnp.ndarray, limit: jnp.ndarray,
                    window: Optional[int] = None,
                    theta: Optional[float] = None,
                    rt=None, layer=None,
                    ) -> Tuple[jnp.ndarray, dict]:
    """Chunked prefill: C tokens of a longer prompt, at absolute positions
    offset[b] .. offset[b] + C - 1, attending everything already written
    into the paged cache (earlier chunks + any shared prefix pages) plus
    the chunk itself. x: (B, C, d); ``offset``/``limit``: (B,) int32 —
    rows at positions >= limit are padding (written to the garbage page,
    outputs discarded by the caller). Reads go through the fp pools only
    (just-written pages are never quantized yet)."""
    b, c, _ = x.shape
    dh = cfg.resolved_head_dim
    theta = theta if theta is not None else cfg.rope_theta
    offset = jnp.asarray(offset, jnp.int32).reshape(-1)
    positions = offset[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    q, k, v = _project_qkv(p, x, cfg, yoco, positions, theta)
    scale = 1.0 / float(dh) ** 0.5
    from repro.runtime import layouts
    layout = layouts.get_layout(cache)
    new_cache = layout.write_chunk(cache, dict(k=k, v=v), offset, limit,
                                   layer=layer)
    use_flash = (rt is not None
                 and getattr(rt, 'attn_impl', 'einsum') == 'flash')
    if use_flash:
        out = layout.flash_chunk(q, new_cache, offset, limit, scale=scale,
                                 window=window, layer=layer)
    else:
        kd, vd = layout.gather_fp(new_cache, layer=layer, width=dh)
        mask = chunk_mask(offset, c, kd.shape[1], window)
        out = _sdpa(q, kd, vd, mask[:, None, None, :, :], scale)
    out = _tp_heads_gather(out.reshape(b, c, -1), rt)
    out = yoco_linear.linear(out, p['wo'], cfg=yoco)
    return out, new_cache


# ----------------------------------------------------------------------------
# MLA (DeepSeek-V3)
# ----------------------------------------------------------------------------
def _mla_qkv_full(p: dict, x: jnp.ndarray, cfg, yoco: YocoConfig,
                  positions: jnp.ndarray):
    """Naive (non-absorbed) q/k/v for train & prefill. Head counts derive
    from the (possibly TP-sharded) ``w_uq``/``w_ukv`` widths, not cfg."""
    m = cfg.mla
    b, s, _ = x.shape
    cq = rmsnorm(yoco_linear.linear(x, p['w_dq'], cfg=yoco), p['q_ln'])
    q = yoco_linear.linear(cq, p['w_uq'], cfg=yoco)
    q = q.reshape(b, s, -1, m.nope_head_dim + m.rope_head_dim)
    q_nope, q_rope = q[..., :m.nope_head_dim], q[..., m.nope_head_dim:]
    q_rope = rope_mod.apply_rope(q_rope, positions, cfg.rope_theta)

    dkv = yoco_linear.linear(x, p['w_dkv'], cfg=yoco)
    ckv = rmsnorm(dkv[..., :m.kv_lora_rank], p['kv_ln'])
    krope = dkv[..., m.kv_lora_rank:]                       # (b, s, d_rope)
    krope = rope_mod.apply_rope(krope[:, :, None, :], positions,
                                cfg.rope_theta)[:, :, 0, :]
    kv = yoco_linear.linear(ckv, p['w_ukv'], cfg=yoco)
    kv = kv.reshape(b, s, -1, m.nope_head_dim + m.v_head_dim)
    k_nope, v = kv[..., :m.nope_head_dim], kv[..., m.nope_head_dim:]
    return q_nope, q_rope, k_nope, krope, v, ckv


def mla_attention(p: dict, x: jnp.ndarray, cfg, yoco: YocoConfig, *,
                  positions: Optional[jnp.ndarray] = None,
                  cache: Optional[dict] = None,
                  rt=None, layer=None,
                  ) -> Tuple[jnp.ndarray, Optional[dict]]:
    """MLA train / prefill (materializes per-head k/v; caches only latents).

    Sequence-parallel layouts gather the LATENT (r + d_rope = 576/token)
    across ranks and expand k/v locally, instead of letting the partitioner
    gather the expanded per-head K/V (2*H*dh = 32768/token) — 56x less
    wire for DeepSeek-V3, at the cost of TP-redundant kv_up compute (the
    paper's keep-it-compressed-on-the-wire principle applied to training;
    see ROADMAP.md)."""
    m = cfg.mla
    b, s, _ = x.shape
    if positions is None:
        positions = rope_mod.default_positions(b, s)
    latent_gather = (rt is not None and rt.mesh is not None
                     and getattr(rt, 'act_layout', 'batch') == '2d'
                     and s % rt.mesh.shape[rt.tp_axis] == 0 and s > 1
                     and cache is None)
    if latent_gather:
        h = cfg.n_heads
        cq = rmsnorm(yoco_linear.linear(x, p['w_dq'], cfg=yoco), p['q_ln'])
        q = yoco_linear.linear(cq, p['w_uq'], cfg=yoco)
        q = q.reshape(b, s, h, m.nope_head_dim + m.rope_head_dim)
        q_nope, q_rope = q[..., :m.nope_head_dim], q[..., m.nope_head_dim:]
        q_rope = rope_mod.apply_rope(q_rope, positions, cfg.rope_theta)
        dkv = yoco_linear.linear(x, p['w_dkv'], cfg=yoco)
        ckv = rmsnorm(dkv[..., :m.kv_lora_rank], p['kv_ln'])
        krope = dkv[..., m.kv_lora_rank:]
        krope = rope_mod.apply_rope(krope[:, :, None, :], positions,
                                    cfg.rope_theta)[:, :, 0, :]
        out = _mla_sdpa_latent_2d(q_nope, q_rope, ckv, krope, p['w_ukv'],
                                  cfg, rt, s)
        out = out.reshape(b, s, -1).astype(x.dtype)
        out = yoco_linear.linear(out, p['wo'], cfg=yoco)
        return out, None
    q_nope, q_rope, k_nope, krope, v, ckv = _mla_qkv_full(
        p, x, cfg, yoco, positions)
    new_cache = None
    if cache is not None:
        from repro.runtime import layouts
        # paged latent layouts scatter ckv ‖ krope as ONE row per token;
        # the registry owns that concatenation discipline
        new_cache = layouts.get_layout(cache).write_prefill(
            cache, dict(ckv=ckv, krope=krope), layer=layer)
    scale = 1.0 / jnp.sqrt(float(m.nope_head_dim + m.rope_head_dim))
    mask = causal_mask(s, s)
    lo = jnp.einsum('bqhd,bshd->bhqs', q_nope, k_nope,
                    preferred_element_type=jnp.float32)
    lo += jnp.einsum('bqhd,bsd->bhqs', q_rope, krope,
                     preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(lo * scale + mask, axis=-1)
    out = jnp.einsum('bhqs,bshd->bqhd', probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    out = _tp_heads_gather(out.reshape(b, s, -1).astype(x.dtype), rt)
    out = yoco_linear.linear(out, p['wo'], cfg=yoco)
    return out, new_cache


def _mla_sdpa_latent_2d(q_nope, q_rope, ckv, krope, w_ukv, cfg, rt, s):
    """shard_map MLA core for sequence-parallel training: each rank
    all_gathers the (r + d_rope)-wide LATENT, expands K/V locally, and
    attends its own query shard. Autodiff transposes the all_gather into a
    psum_scatter ON THE LATENT — the dK/dV reduction never materializes at
    2*H*dh width (see ROADMAP.md)."""
    m = cfg.mla
    h = cfg.n_heads
    tp = rt.tp_axis
    scale = 1.0 / jnp.sqrt(float(m.nope_head_dim + m.rope_head_dim))
    from jax.sharding import PartitionSpec as P

    def core(qn, qr, ck, kr, wukv):
        ck_f = jax.lax.all_gather(ck, tp, axis=1, tiled=True)   # (bl, s, r)
        kr_f = jax.lax.all_gather(kr, tp, axis=1, tiled=True)
        w = wukv.reshape(m.kv_lora_rank, h,
                         m.nope_head_dim + m.v_head_dim).astype(qn.dtype)
        kv = jnp.einsum('bsr,rhd->bshd', ck_f, w,
                        preferred_element_type=jnp.float32).astype(qn.dtype)
        kn, v = kv[..., :m.nope_head_dim], kv[..., m.nope_head_dim:]
        lo = jnp.einsum('bqhd,bshd->bhqs', qn, kn,
                        preferred_element_type=jnp.float32)
        lo += jnp.einsum('bqhd,bsd->bhqs', qr, kr_f,
                         preferred_element_type=jnp.float32)
        sl = qn.shape[1]
        offset = jax.lax.axis_index(tp) * sl
        mask = causal_mask(sl, s, offset)
        probs = jax.nn.softmax(lo * scale + mask, axis=-1)
        out = jnp.einsum('bhqs,bshd->bqhd', probs.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        return out.astype(qn.dtype)

    dp = rt.dp_axes
    return jax.shard_map(
        core, mesh=rt.mesh,
        in_specs=(P(dp, tp, None, None), P(dp, tp, None, None),
                  P(dp, tp, None), P(dp, tp, None), P()),
        out_specs=P(dp, tp, None, None),
        check_vma=False,
    )(q_nope, q_rope, ckv, krope, w_ukv)


def mla_absorbed_attend(q_lat: jnp.ndarray, q_rope: jnp.ndarray,
                        ckv: jnp.ndarray, krope: jnp.ndarray, pos,
                        scale: float) -> jnp.ndarray:
    """Absorbed latent-space decode attention core — THE einsum oracle the
    paged MLA flash kernel is validated against (tests and benchmarks call
    this exact function, not a re-assembled copy).

    q_lat: (B, 1, H, r) — q_nope already absorbed through W_uk;
    q_rope: (B, 1, H, d_rope); ckv/krope: (B, S, r) / (B, S, d_rope) dense
    latent views; pos scalar or (B,). Math runs in f32 (latent scores carry
    r-deep dot products); returns the (B, 1, H, r) latent output, BEFORE
    the W_uv up-projection."""
    lo = jnp.einsum('bqhr,bsr->bhqs', q_lat.astype(jnp.float32),
                    ckv.astype(jnp.float32))
    lo += jnp.einsum('bqhd,bsd->bhqs', q_rope.astype(jnp.float32),
                     krope.astype(jnp.float32))
    mask = decode_mask(pos, ckv.shape[1])
    if jnp.ndim(pos) != 0:
        mask = mask[:, None, None, :]               # lo is (b, h, q, s)
    probs = jax.nn.softmax(lo * scale + mask, axis=-1)
    return jnp.einsum('bhqs,bsr->bqhr', probs, ckv.astype(jnp.float32))


def mla_attention_decode(p: dict, x: jnp.ndarray, cfg, yoco: YocoConfig, *,
                         cache: dict, pos: jnp.ndarray, rt=None,
                         layer=None,
                         ) -> Tuple[jnp.ndarray, dict]:
    """Absorbed MLA decode: attention runs in the latent space.

    scores = (q_nope @ W_uk) · ckv + q_rope · krope      (per head)
    out    = (probs · ckv) @ W_uv                        (per head)

    The KV cache stores only (ckv, krope) — r + d_rope = 576 values/token for
    DeepSeek-V3 vs 2·128·128 = 32768 for naive GQA: the paper's 'keep it
    compressed until the last moment' on the memory side.

    ``pos``: scalar int or (B,) vector of per-request absolute positions.

    The cache's :class:`~repro.runtime.layouts.CacheLayout` routes the
    read: paged latent layouts under ``rt.attn_impl == 'flash'`` go
    through their kernel entrypoint (``flash_decode_paged_mla`` /
    ``_mla_q8`` — dead latent tiles neither computed nor fetched),
    everything else through the densified :func:`mla_absorbed_attend`
    oracle (tier-mixing for the quantized layout). Either way W_uv is
    applied once, outside the softmax loop."""
    m = cfg.mla
    b = x.shape[0]
    if jnp.ndim(pos) == 0:
        positions = jnp.full((b, 1), pos, jnp.int32)
    else:
        positions = jnp.asarray(pos, jnp.int32).reshape(b, 1)
    cq = rmsnorm(yoco_linear.linear(x, p['w_dq'], cfg=yoco), p['q_ln'])
    q = yoco_linear.linear(cq, p['w_uq'], cfg=yoco)
    # -1: the local head count under TP sharding (w_uq split by head)
    q = q.reshape(b, 1, -1, m.nope_head_dim + m.rope_head_dim)
    q_nope, q_rope = q[..., :m.nope_head_dim], q[..., m.nope_head_dim:]
    q_rope = rope_mod.apply_rope(q_rope, positions, cfg.rope_theta)

    dkv = yoco_linear.linear(x, p['w_dkv'], cfg=yoco)
    ckv_t = rmsnorm(dkv[..., :m.kv_lora_rank], p['kv_ln'])
    krope_t = dkv[..., m.kv_lora_rank:]
    krope_t = rope_mod.apply_rope(krope_t[:, :, None, :], positions,
                                  cfg.rope_theta)[:, :, 0, :]

    # absorb W_uk into q: (b,1,h,dn) @ (r, h, dn) -> (b,1,h,r)
    w_ukv = p['w_ukv'].reshape(m.kv_lora_rank, -1,
                               m.nope_head_dim + m.v_head_dim)
    w_uk = w_ukv[..., :m.nope_head_dim]                    # (r, h, dn)
    w_uv = w_ukv[..., m.nope_head_dim:]                    # (r, h, dv)
    q_lat = jnp.einsum('bqhd,rhd->bqhr', q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    # python float, not a traced jnp scalar: the flash kernel takes it as a
    # static (hashable) argument
    scale = 1.0 / float(m.nope_head_dim + m.rope_head_dim) ** 0.5
    use_flash = (rt is not None
                 and getattr(rt, 'attn_impl', 'einsum') == 'flash')

    from repro.runtime import layouts
    layout = layouts.get_layout(cache)
    r = m.kv_lora_rank
    posr = (jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
            if layout.paged else pos)
    # writes always land in the fp latent pool, quantized layout or not
    new_cache = layout.write_token(cache, dict(ckv=ckv_t, krope=krope_t),
                                   posr, layer=layer)
    if use_flash and layout.paged:
        o_lat = layout.flash_decode(
            jnp.concatenate([q_lat, q_rope.astype(jnp.float32)], -1),
            new_cache, posr, scale=scale, r=r, layer=layer)
    else:
        # absorbed einsum oracle on the layout's densified (tier-mixing)
        # latent view (the MLA flash kernels are paged-only)
        ckv_d, krope_d = layout.gather(new_cache, posr, r=r, layer=layer,
                                       width=r + m.rope_head_dim)
        o_lat = mla_absorbed_attend(q_lat, q_rope, ckv_d, krope_d, posr,
                                    scale)

    out = jnp.einsum('bqhr,rhd->bqhd', o_lat, w_uv.astype(jnp.float32))
    out = _tp_heads_gather(out.reshape(b, 1, -1).astype(x.dtype), rt)
    out = yoco_linear.linear(out, p['wo'], cfg=yoco)
    return out, new_cache


def mla_attention_chunk(p: dict, x: jnp.ndarray, cfg, yoco: YocoConfig, *,
                        cache: dict, offset: jnp.ndarray,
                        limit: jnp.ndarray, rt=None, layer=None,
                        ) -> Tuple[jnp.ndarray, dict]:
    """Chunked MLA prefill through the absorbed decode math: C tokens at
    absolute positions offset[b] .. offset[b] + C - 1 attend the paged
    latent cache (earlier chunks + shared prefix pages + the chunk
    itself). Same contract as :func:`attention_chunk`; reads are fp-pool
    only and W_uv is applied once, outside the softmax."""
    m = cfg.mla
    b, c, _ = x.shape
    offset = jnp.asarray(offset, jnp.int32).reshape(-1)
    positions = offset[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    cq = rmsnorm(yoco_linear.linear(x, p['w_dq'], cfg=yoco), p['q_ln'])
    q = yoco_linear.linear(cq, p['w_uq'], cfg=yoco)
    q = q.reshape(b, c, -1, m.nope_head_dim + m.rope_head_dim)
    q_nope, q_rope = q[..., :m.nope_head_dim], q[..., m.nope_head_dim:]
    q_rope = rope_mod.apply_rope(q_rope, positions, cfg.rope_theta)

    dkv = yoco_linear.linear(x, p['w_dkv'], cfg=yoco)
    ckv_t = rmsnorm(dkv[..., :m.kv_lora_rank], p['kv_ln'])
    krope_t = dkv[..., m.kv_lora_rank:]
    krope_t = rope_mod.apply_rope(krope_t[:, :, None, :], positions,
                                  cfg.rope_theta)[:, :, 0, :]

    w_ukv = p['w_ukv'].reshape(m.kv_lora_rank, -1,
                               m.nope_head_dim + m.v_head_dim)
    w_uk = w_ukv[..., :m.nope_head_dim]
    w_uv = w_ukv[..., m.nope_head_dim:]
    q_lat = jnp.einsum('bqhd,rhd->bqhr', q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    scale = 1.0 / float(m.nope_head_dim + m.rope_head_dim) ** 0.5

    from repro.runtime import layouts
    layout = layouts.get_layout(cache)
    r = m.kv_lora_rank
    new_cache = layout.write_chunk(cache, dict(ckv=ckv_t, krope=krope_t),
                                   offset, limit, layer=layer)
    use_flash = (rt is not None
                 and getattr(rt, 'attn_impl', 'einsum') == 'flash')
    if use_flash and layout.paged:
        o_lat = layout.flash_chunk(
            jnp.concatenate([q_lat, q_rope.astype(jnp.float32)], -1),
            new_cache, offset, limit, scale=scale, r=r, layer=layer)
    else:
        ckv_d, krope_d = layout.gather_fp(new_cache, r=r, layer=layer,
                                          width=r + m.rope_head_dim)
        lo = jnp.einsum('bqhr,bsr->bhqs', q_lat,
                        ckv_d.astype(jnp.float32))
        lo += jnp.einsum('bqhd,bsd->bhqs', q_rope.astype(jnp.float32),
                         krope_d.astype(jnp.float32))
        mask = chunk_mask(offset, c, ckv_d.shape[1])
        probs = jax.nn.softmax(lo * scale + mask[:, None, :, :], axis=-1)
        o_lat = jnp.einsum('bhqs,bsr->bqhr', probs,
                           ckv_d.astype(jnp.float32))

    out = jnp.einsum('bqhr,rhd->bqhd', o_lat, w_uv.astype(jnp.float32))
    out = _tp_heads_gather(out.reshape(b, c, -1).astype(x.dtype), rt)
    out = yoco_linear.linear(out, p['wo'], cfg=yoco)
    return out, new_cache
