"""Model assembly: every assigned architecture as one CausalLM built from a
``repro.configs.ArchConfig``. Entry points:

  init_params(key, cfg)                      -> param pytree (layers stacked)
  forward(params, batch, cfg, yoco, rt)      -> (logits, metrics)      [train]
  loss_fn(params, batch, cfg, yoco, rt)      -> (loss, metrics)
  init_cache_tree(cfg, batch, max_seq)       -> cache pytree
  prefill(params, batch, cache, cfg, ...)    -> (last_logits, cache)
  decode_step(params, token, pos, cache, ..) -> (logits, cache)

Layer stacks are scanned (``jax.lax.scan`` over stacked params) so the HLO
stays compact at 61-80 layers; heterogeneity (gemma3 local/global pattern,
deepseek dense-prefix) is expressed as per-layer scan inputs or separate
stacks. A serving cache rides in the scan carry and each layer indexes
it in place (see ``_transformer_stack``). Optional remat wraps the scan
body (``rt.remat``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.yoco_linear import YocoConfig, DEFAULT_YOCO
from repro.models import attention as attn_mod
from repro.models import blocks as blk
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import apply_norm, embed_init, init_norm, dense_init


# ----------------------------------------------------------------------------
# runtime context (distribution knobs threaded through the model)
# ----------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ModelRuntime:
    mesh: Any = None               # jax.sharding.Mesh or None (single host)
    dp_axes: tuple = ('data',)     # batch axes; ('pod','data') multi-pod
    tp_axis: str = 'model'
    use_ep: bool = False           # expert-parallel MoE (needs mesh)
    remat: str = 'none'            # none | full | dots
    act_layout: str = 'batch'      # batch (TP baseline) | 2d (batch x seq)
    attn_impl: str = 'einsum'      # einsum (oracle) | flash (Pallas decode)
    compute_dtype: Any = jnp.bfloat16
    # set INSIDE a serving shard_map body (mesh stays None there): the named
    # mesh axis the attention output's head shards are all-gathered over —
    # the ONE collective per layer of the TP serving path (see
    # runtime/serve_step.py tp_* builders)
    tp_reduce: Optional[str] = None

    @property
    def moe_ctx(self) -> Optional[moe_mod.EPContext]:
        if self.use_ep and self.mesh is not None:
            return moe_mod.EPContext(self.mesh, self.dp_axes, self.tp_axis)
        return None


DEFAULT_RT = ModelRuntime()


def _constrain(x: jnp.ndarray, rt: ModelRuntime, *,
               last_axis: Optional[str] = None) -> jnp.ndarray:
    """Anchor activation sharding: batch over dp axes, optional last-dim
    axis (vocab over tp for logits). Without these anchors auto-SPMD happily
    chooses batch-replicated/feature-sharded activations, which turns every
    row-parallel matmul into a full-microbatch all-reduce (see
    ROADMAP.md)."""
    if rt.mesh is None:
        return x
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    dp_size = int(np.prod([rt.mesh.shape[a] for a in rt.dp_axes]))
    bdim = rt.dp_axes if x.shape[0] % dp_size == 0 and x.shape[0] > 1 else None
    spec = [bdim] + [None] * (x.ndim - 1)
    tp = rt.mesh.shape[rt.tp_axis]
    if (rt.act_layout == '2d' and x.ndim >= 3
            and x.shape[1] % tp == 0 and x.shape[1] > 1):
        # §Perf 'fsdp2d': shard the sequence dim over 'model' too — no TP
        # activation all-reduces; attention gathers K/V instead
        spec[1] = rt.tp_axis
    elif last_axis is not None \
            and x.shape[-1] % rt.mesh.shape[last_axis] == 0:
        spec[-1] = last_axis
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rt.mesh, P(*spec)))

_REMAT_POLICIES = {
    'full': None,                                        # save nothing
    'dots': 'dots_with_no_batch_dims_saveable',
}


def _maybe_remat(fn, rt: ModelRuntime):
    if rt.remat == 'none':
        return fn
    pol = _REMAT_POLICIES[rt.remat]
    if isinstance(pol, str):
        pol = getattr(jax.checkpoint_policies, pol)
    return jax.checkpoint(fn, policy=pol)


# ----------------------------------------------------------------------------
# per-arch structural helpers
# ----------------------------------------------------------------------------
def _n_sites(cfg) -> int:
    return cfg.n_layers // cfg.hybrid_group if cfg.hybrid_group else 0


def _n_mamba(cfg) -> int:
    """Hybrid archs: sequence-mixing layers that are Mamba2 (rest are shared-
    attention applications)."""
    if cfg.family == 'ssm':
        return cfg.n_layers
    if cfg.hybrid_group:
        return cfg.n_layers - _n_sites(cfg)
    return 0


def _gemma_layer_meta(cfg):
    """(window, theta) per layer for the local/global pattern. Global layers
    get window = max_seq_len (never binds) + the long-rope theta."""
    L = cfg.n_layers
    every = cfg.local_global_every
    idx = jnp.arange(L)
    is_global = (idx % every) == (every - 1) if every else jnp.zeros(L, bool)
    big = jnp.int32(cfg.max_seq_len + 1)
    window = jnp.where(is_global, big, jnp.int32(cfg.sliding_window or big))
    theta = jnp.where(is_global,
                      jnp.float32(cfg.global_rope_theta or cfg.rope_theta),
                      jnp.float32(cfg.rope_theta))
    return window, theta


def _stack_init(init_fn, key: jax.Array, n: int):
    return jax.vmap(init_fn)(jax.random.split(key, n))


# ----------------------------------------------------------------------------
# init
# ----------------------------------------------------------------------------
def init_params(key: jax.Array, cfg) -> dict:
    k_emb, k_layers, k_head, k_shared, k_prefix = jax.random.split(key, 5)
    p: dict = {}
    # embeddings
    if cfg.input_kind == 'codebooks':
        p['embed'] = jax.vmap(lambda k: embed_init(k, cfg.vocab_size,
                                                   cfg.d_model))(
            jax.random.split(k_emb, cfg.n_codebooks))
    elif cfg.input_kind == 'tokens':
        p['embed'] = embed_init(k_emb, cfg.vocab_size, cfg.d_model)
    # layers
    if cfg.family == 'ssm':
        p['layers'] = _stack_init(lambda k: blk.init_mamba_block(k, cfg),
                                  k_layers, cfg.n_layers)
    elif cfg.hybrid_group:
        p['layers'] = _stack_init(lambda k: blk.init_mamba_block(k, cfg),
                                  k_layers, _n_mamba(cfg))
        p['shared'] = blk.init_shared_block(k_shared, cfg, _n_sites(cfg))
    elif cfg.moe is not None:
        n_moe = cfg.n_layers - cfg.moe.first_k_dense
        p['layers'] = _stack_init(
            lambda k: blk.init_transformer_block(k, cfg, use_moe=True),
            k_layers, n_moe)
        if cfg.moe.first_k_dense:
            p['dense_prefix'] = _stack_init(
                lambda k: blk.init_transformer_block(k, cfg, use_moe=False),
                k_prefix, cfg.moe.first_k_dense)
    else:
        p['layers'] = _stack_init(
            lambda k: blk.init_transformer_block(k, cfg, use_moe=False),
            k_layers, cfg.n_layers)
    # final norm + head
    p['final_norm'] = init_norm(cfg)
    if cfg.input_kind == 'codebooks':
        p['lm_head'] = jax.vmap(
            lambda k: dense_init(k, cfg.d_model, cfg.vocab_size))(
            jax.random.split(k_head, cfg.n_codebooks))
    elif not cfg.tie_embeddings:
        p['lm_head'] = dense_init(k_head, cfg.d_model, cfg.vocab_size)
    return p


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ----------------------------------------------------------------------------
# embedding / head
# ----------------------------------------------------------------------------
def _embed(params: dict, batch: dict, cfg, rt: ModelRuntime) -> jnp.ndarray:
    dt = rt.compute_dtype
    if cfg.input_kind == 'embeddings':
        return batch['inputs'].astype(dt)
    toks = batch['inputs']
    if cfg.input_kind == 'codebooks':
        parts = [jnp.take(params['embed'][c], toks[..., c], axis=0)
                 for c in range(cfg.n_codebooks)]
        return sum(parts).astype(dt)
    return jnp.take(params['embed'], toks, axis=0).astype(dt)


def _head(params: dict, x: jnp.ndarray, cfg, yoco: YocoConfig) -> jnp.ndarray:
    if cfg.input_kind == 'codebooks':
        return jnp.einsum('bsd,cdv->bscv', x,
                          params['lm_head'].astype(x.dtype))
    w = params['embed'].T if cfg.tie_embeddings else params['lm_head']
    from repro.core import yoco_linear
    return yoco_linear.yoco_matmul(x, w.astype(x.dtype) if cfg.tie_embeddings
                                   else w, yoco)


# ----------------------------------------------------------------------------
# layer-stack drivers (train / prefill / decode share these)
# ----------------------------------------------------------------------------
def _transformer_stack(stack: dict, x: jnp.ndarray, cfg, yoco, rt, *,
                       cache: Optional[dict], decode_pos, use_moe: bool,
                       chunk_ctx=None):
    """Scan a homogeneous transformer stack. Returns (x, new_cache,
    aux_sum).

    ``cache`` (every leaf stacked (L, ...), or None for training) rides in
    the scan CARRY, whole: the scan runs over (params, layer index) and
    each layer's block reads and writes its own layer of the stacked
    pools in place — writes at ``[l, page, row]``, the flash kernels DMA
    layer ``l``'s live pages straight out of the stack, the small
    ``bt``/``hw`` copies are read at ``[l]``. Slicing the pools as scan
    inputs and restacking them as scan outputs would instead copy every
    layer's pool out and back, and the whole stack once more, on every
    step program."""
    gemma = cfg.local_global_every > 0
    n = jax.tree.leaves(stack)[0].shape[0]
    window = theta = None
    if gemma:
        window, theta = _gemma_layer_meta(cfg)
        window, theta = window[:n], theta[:n]
    moe_ctx = rt.moe_ctx

    def body(carry, xs):
        h, aux, c = carry
        lp, layer, win, th = xs
        if not gemma:
            win = cfg.sliding_window
        h, c, metrics = blk.transformer_block(
            lp, h, cfg, yoco, window=win, theta=th, cache=c,
            decode_pos=decode_pos, moe_ctx=moe_ctx, rt=rt,
            chunk_ctx=chunk_ctx, layer=layer)
        h = _constrain(h, rt)
        aux = aux + (metrics.get('aux_loss', 0.0) if use_moe else 0.0)
        return (h, aux, c), None

    body = _maybe_remat(body, rt)
    layers = None if cache is None else jnp.arange(n, dtype=jnp.int32)
    (x, aux, new_cache), _ = jax.lax.scan(
        body, (x, jnp.float32(0.0), cache), (stack, layers, window, theta))
    return x, new_cache, aux


def _mamba_stack(stack: dict, x: jnp.ndarray, cfg, yoco, rt, *,
                 state: Optional[dict], decode: bool, last_pos=None):
    def body(carry, xs):
        lp, st = xs
        h, new_st = blk.mamba_block(lp, carry, cfg, yoco, state=st,
                                    decode=decode, last_pos=last_pos)
        return _constrain(h, rt), new_st

    body = _maybe_remat(body, rt)
    x, new_state = jax.lax.scan(body, x, (stack, state))
    return x, new_state


def _tree_slice(tree, lo: int, hi: int):
    return jax.tree.map(lambda a: a[lo:hi], tree)


def _backbone(params: dict, x: jnp.ndarray, cfg, yoco, rt, *,
              cache: Optional[dict], decode_pos, last_pos=None,
              chunk_ctx=None):
    """Run all sequence-mixing layers. Returns (x, new_cache, aux).

    ``last_pos`` (prefill only): per-request last valid prompt positions
    of a right-padded batch. Attention layers ignore it (the causal mask
    plus decode's write-before-attend already keep padded keys inert) but
    mamba layers must mask the padded steps' dt to 0 so the recurrent
    state snapshot equals the unpadded prompt's state.

    ``chunk_ctx`` (dict(offset=, limit=)) runs the attention layers in
    chunked-prefill mode — attention-only families (recurrent state has
    no random-access positions to resume a chunk from)."""
    aux = jnp.float32(0.0)
    new_cache: Optional[dict] = None
    if decode_pos is not None:
        last_pos = None     # decode steps have no padding to mask
    if chunk_ctx is not None and (cfg.family == 'ssm' or cfg.hybrid_group):
        raise NotImplementedError(
            f'chunked prefill needs random-access cache positions; '
            f'family={cfg.family!r} carries recurrent state')
    if cfg.family == 'ssm':
        st = cache['ssm'] if cache is not None else None
        x, new_st = _mamba_stack(params['layers'], x, cfg, yoco, rt,
                                 state=st, decode=decode_pos is not None,
                                 last_pos=last_pos)
        new_cache = dict(ssm=new_st) if cache is not None else None
    elif cfg.hybrid_group:
        x0 = x
        n_sites = _n_sites(cfg)
        per = cfg.hybrid_group - 1
        st = cache['ssm'] if cache is not None else None
        atc = cache['attn'] if cache is not None else None
        new_st, new_at = [], []
        decode = decode_pos is not None
        for g in range(n_sites):
            lo, hi = g * per, (g + 1) * per
            seg = _tree_slice(params['layers'], lo, hi)
            seg_st = _tree_slice(st, lo, hi) if st is not None else None
            x, ns = _mamba_stack(seg, x, cfg, yoco, rt, state=seg_st,
                                 decode=decode, last_pos=last_pos)
            if ns is not None and cache is not None:
                new_st.append(ns)
            site_cache = (jax.tree.map(lambda a: a[g], atc)
                          if atc is not None else None)
            x, nc = blk.shared_block(params['shared'], x, x0, g, cfg, yoco,
                                     cache=site_cache, decode_pos=decode_pos,
                                     rt=rt)
            if nc is not None and cache is not None:
                new_at.append(nc)
        tail = _n_mamba(cfg) - n_sites * per
        if tail:
            lo = n_sites * per
            seg = _tree_slice(params['layers'], lo, lo + tail)
            seg_st = _tree_slice(st, lo, lo + tail) if st is not None else None
            x, ns = _mamba_stack(seg, x, cfg, yoco, rt, state=seg_st,
                                 decode=decode, last_pos=last_pos)
            if ns is not None and cache is not None:
                new_st.append(ns)
        if cache is not None:
            new_cache = dict(
                ssm=jax.tree.map(lambda *a: jnp.concatenate(a, 0), *new_st),
                attn=jax.tree.map(lambda *a: jnp.stack(a, 0), *new_at),
            )
    elif cfg.moe is not None and cfg.moe.first_k_dense:
        pc = cache['prefix'] if cache is not None else None
        mc = cache['moe'] if cache is not None else None
        x, npc, _ = _transformer_stack(params['dense_prefix'], x, cfg, yoco,
                                       rt, cache=pc, decode_pos=decode_pos,
                                       use_moe=False, chunk_ctx=chunk_ctx)
        x, nmc, aux = _transformer_stack(params['layers'], x, cfg, yoco, rt,
                                         cache=mc, decode_pos=decode_pos,
                                         use_moe=True, chunk_ctx=chunk_ctx)
        if cache is not None:
            new_cache = dict(prefix=npc, moe=nmc)
    else:
        use_moe = cfg.moe is not None
        lc = cache['layers'] if cache is not None else None
        x, nlc, aux = _transformer_stack(params['layers'], x, cfg, yoco, rt,
                                         cache=lc, decode_pos=decode_pos,
                                         use_moe=use_moe,
                                         chunk_ctx=chunk_ctx)
        if cache is not None:
            new_cache = dict(layers=nlc)
    return x, new_cache, aux


# ----------------------------------------------------------------------------
# cache construction
# ----------------------------------------------------------------------------
def init_cache_tree(cfg, batch: int, max_seq: int, dtype=jnp.bfloat16) -> dict:
    """Stacked per-layer caches matching ``_backbone``'s expectations."""
    def attn_caches(n):
        one = attn_mod.init_cache(cfg, batch, max_seq, dtype)
        return jax.tree.map(lambda a: jnp.broadcast_to(a[None],
                                                       (n,) + a.shape).copy(),
                            one)

    def ssm_states(n):
        one = ssm_mod.init_ssm_state(cfg, batch)
        return jax.tree.map(lambda a: jnp.broadcast_to(a[None],
                                                       (n,) + a.shape).copy(),
                            one)

    if cfg.family == 'ssm':
        return dict(ssm=ssm_states(cfg.n_layers))
    if cfg.hybrid_group:
        return dict(ssm=ssm_states(_n_mamba(cfg)),
                    attn=attn_caches(_n_sites(cfg)))
    if cfg.moe is not None and cfg.moe.first_k_dense:
        return dict(prefix=attn_caches(cfg.moe.first_k_dense),
                    moe=attn_caches(cfg.n_layers - cfg.moe.first_k_dense))
    return dict(layers=attn_caches(cfg.n_layers))


def init_paged_cache_tree(cfg, batch: int, *, num_pages: int,
                          page_size: int, max_blocks: int,
                          dtype=jnp.bfloat16,
                          kv_dtype: Optional[str] = None,
                          hot_window: int = 1) -> dict:
    """Paged-cache analogue of :func:`init_cache_tree`: each attention
    layer gets its own physical pool, stacked over L into one buffer per
    leaf that the layer scan carries whole and indexes by layer in place
    (see :func:`_transformer_stack`). Every layer shares the same logical
    block tables: the ``bt`` leaf keeps one small copy per layer, read at
    the scan's layer index (``runtime.kv_cache.with_block_tables``
    refreshes every copy when the scheduler reassigns pages).

    ``kv_dtype='int8'`` builds the hybrid-precision tier layouts
    (``runtime.layouts.PagedQ8Layout`` / ``PagedMLAQ8Layout``): per-layer
    int8 pools + scale leaves and the per-layer ``hw`` hot-window knob,
    alongside the fp pools. MLA configs get one latent
    ``cl`` pool per layer instead of k/v pairs; their int8 tier quantizes
    the latent per-page absmax before the W_uk/W_uv expansion.

    SSM configs get a stacked per-slot recurrent state instead
    (``runtime.layouts.RecurrentLayout``: f32 ``conv``/``ssm`` leaves, no
    positional axis — the scheduler's page accounting is purely virtual);
    hybrid configs mix a recurrent ``ssm`` stack with paged ``attn`` site
    pools under ``runtime.layouts.HybridLayout``. Recurrent state carries
    no int8 tier, so ``kv_dtype='int8'`` on a pure-SSM config is an
    error (hybrid configs apply it to the attention sites only)."""
    def recurrent_states(n):
        one = ssm_mod.init_ssm_state(cfg, batch)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n,) + a.shape)
            .astype(jnp.float32).copy(), one)

    if cfg.family == 'ssm':
        if kv_dtype is not None:
            raise ValueError(
                'recurrent state has no int8 tier; drop kv_dtype for '
                f'family={cfg.family!r}')
        return dict(ssm=recurrent_states(cfg.n_layers))

    def paged_caches(n):
        one = attn_mod.init_paged_cache(cfg, batch, num_pages=num_pages,
                                        page_size=page_size,
                                        max_blocks=max_blocks, dtype=dtype,
                                        kv_dtype=kv_dtype,
                                        hot_window=hot_window)
        return jax.tree.map(lambda a: jnp.broadcast_to(a[None],
                                                       (n,) + a.shape).copy(),
                            one)

    if cfg.hybrid_group:
        return dict(ssm=recurrent_states(_n_mamba(cfg)),
                    attn=paged_caches(_n_sites(cfg)))
    if cfg.moe is not None and cfg.moe.first_k_dense:
        return dict(prefix=paged_caches(cfg.moe.first_k_dense),
                    moe=paged_caches(cfg.n_layers - cfg.moe.first_k_dense))
    return dict(layers=paged_caches(cfg.n_layers))


# ----------------------------------------------------------------------------
# public entry points
# ----------------------------------------------------------------------------
def forward(params: dict, batch: dict, cfg, yoco: YocoConfig = DEFAULT_YOCO,
            rt: ModelRuntime = DEFAULT_RT) -> Tuple[jnp.ndarray, dict]:
    """Training forward: full-sequence causal logits."""
    x = _constrain(_embed(params, batch, cfg, rt), rt)
    x, _, aux = _backbone(params, x, cfg, yoco, rt, cache=None,
                          decode_pos=None)
    x = apply_norm(params['final_norm'], x, cfg)
    logits = _constrain(_head(params, x, cfg, yoco), rt, last_axis=rt.tp_axis)
    return logits, dict(moe_aux_loss=aux)


def loss_fn(params: dict, batch: dict, cfg,
            yoco: YocoConfig = DEFAULT_YOCO,
            rt: ModelRuntime = DEFAULT_RT) -> Tuple[jnp.ndarray, dict]:
    """Next-token cross-entropy (f32), averaged over non-masked positions.
    labels < 0 are masked. MoE aux loss added with the config weight."""
    logits, metrics = forward(params, batch, cfg, yoco, rt)
    labels = batch['labels']
    lf = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(
        lf, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    nll = logz - gold
    mask = (labels >= 0).astype(jnp.float32)
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    total = loss
    if cfg.moe is not None:
        total = total + cfg.moe.router_aux_weight * metrics['moe_aux_loss']
    metrics = dict(metrics, ce_loss=loss, total_loss=total,
                   tokens=jnp.sum(mask))
    return total, metrics


def prefill(params: dict, batch: dict, cache: dict, cfg,
            yoco: YocoConfig = DEFAULT_YOCO,
            rt: ModelRuntime = DEFAULT_RT,
            last_pos=None) -> Tuple[jnp.ndarray, dict]:
    """Process the prompt, fill the cache, return last-position logits.

    ``last_pos``: optional (B,) int vector of per-request last prompt
    positions (ragged batch padded to a common length) — logits are
    gathered there instead of at the padded end, and mamba layers mask
    the padded steps so the recurrent state matches the unpadded
    prompt's."""
    x = _embed(params, batch, cfg, rt)
    x, new_cache, _ = _backbone(params, x, cfg, yoco, rt, cache=cache,
                                decode_pos=None, last_pos=last_pos)
    if last_pos is None:
        x = x[:, -1:]
    else:
        idx = jnp.asarray(last_pos, jnp.int32).reshape(-1, 1, 1)
        x = jnp.take_along_axis(x, idx, axis=1)
    x = apply_norm(params['final_norm'], x, cfg)
    logits = _head(params, x, cfg, yoco)
    return logits[:, 0], new_cache


def prefill_chunk(params: dict, batch: dict, offset, limit, cache: dict,
                  cfg, yoco: YocoConfig = DEFAULT_YOCO,
                  rt: ModelRuntime = DEFAULT_RT) -> Tuple[jnp.ndarray, dict]:
    """Process ONE C-token chunk of a longer prompt into a paged cache.

    ``batch['inputs']``: the chunk's (B, C) tokens; ``offset``/``limit``:
    (B,) int32 — the chunk covers absolute positions
    [offset, min(offset + C, limit)); rows past ``limit`` are padding
    (written to the garbage page, excluded from attention by every other
    row's causal mask). Earlier chunks — and any shared prefix pages the
    scheduler pointed the block table at — are already in the cache, so
    chunk k attends [0, offset_k + C) exactly like a monolithic prefill
    would. Returns logits gathered at the chunk row holding position
    ``limit - 1`` (meaningful on the final chunk only) and the updated
    cache. Attention-only families."""
    x = _embed(params, batch, cfg, rt)
    c = x.shape[1]
    b = x.shape[0]
    offset = jnp.broadcast_to(
        jnp.asarray(offset, jnp.int32).reshape(-1), (b,))
    limit = jnp.broadcast_to(
        jnp.asarray(limit, jnp.int32).reshape(-1), (b,))
    x, new_cache, _ = _backbone(params, x, cfg, yoco, rt, cache=cache,
                                decode_pos=None,
                                chunk_ctx=dict(offset=offset, limit=limit))
    idx = jnp.clip(limit - 1 - offset, 0, c - 1).reshape(-1, 1, 1)
    x = jnp.take_along_axis(x, idx, axis=1)
    x = apply_norm(params['final_norm'], x, cfg)
    logits = _head(params, x, cfg, yoco)
    return logits[:, 0], new_cache


def decode_step(params: dict, token, pos, cache: dict, cfg,
                yoco: YocoConfig = DEFAULT_YOCO,
                rt: ModelRuntime = DEFAULT_RT) -> Tuple[jnp.ndarray, dict]:
    """One decode step. ``token``: (B,) int (or (B, CB) codebooks, or (B, d)
    embeddings); ``pos``: scalar int32 — current absolute position — or a
    (B,) vector of per-request positions (heterogeneous batched decode)."""
    if cfg.input_kind == 'embeddings':
        batch = dict(inputs=token[:, None, :])
    elif cfg.input_kind == 'codebooks':
        batch = dict(inputs=token[:, None, :])
    else:
        batch = dict(inputs=token[:, None])
    x = _embed(params, batch, cfg, rt)
    x, new_cache, _ = _backbone(params, x, cfg, yoco, rt, cache=cache,
                                decode_pos=pos)
    x = apply_norm(params['final_norm'], x, cfg)
    logits = _head(params, x, cfg, yoco)
    return logits[:, 0], new_cache
