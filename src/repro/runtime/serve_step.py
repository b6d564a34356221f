"""Serving-step builders: batched prefill and single-token decode with
sharded KV caches. The decode step is what the ``decode_32k`` / ``long_500k``
dry-run cells lower.

Batched serving: the decode step's ``pos`` argument is a scalar for
lock-step batches or a (B,) vector for heterogeneous-position batches
(each request at its own point in its stream — the shape continuous
batching needs). ``rt.attn_impl='flash'`` routes the cache read through
the fused Pallas flash-decode kernel."""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.yoco_linear import YocoConfig, DEFAULT_YOCO
from repro.distributed import sharding
from repro.models import model as model_mod
from repro.models.model import ModelRuntime, DEFAULT_RT


def make_prefill_step(cfg, yoco: YocoConfig = DEFAULT_YOCO,
                      rt: ModelRuntime = DEFAULT_RT):
    def prefill_step(params, batch, cache, last_pos=None):
        return model_mod.prefill(params, batch, cache, cfg, yoco, rt,
                                 last_pos=last_pos)
    return prefill_step


def make_chunk_prefill_step(cfg, yoco: YocoConfig = DEFAULT_YOCO,
                            rt: ModelRuntime = DEFAULT_RT):
    """Chunked-prefill step: one C-token slice of a longer prompt at
    absolute positions [offset, min(offset + C, limit)). The driver loops
    this over a prompt's chunks (C stays constant per jit signature) so
    long-prompt admission interleaves with the decode batch instead of
    stalling it, and prefix-cache hits prefill only the unshared suffix.
    Returns (last-chunk-row logits, cache) — logits meaningful on the
    final chunk only. Attention-only families."""
    def chunk_prefill_step(params, batch, offset, limit, cache):
        return model_mod.prefill_chunk(params, batch, offset, limit, cache,
                                       cfg, yoco, rt)
    return chunk_prefill_step


def sample_tokens(logits: jnp.ndarray, key: jax.Array, *,
                  temperature: float = 1.0, top_k: int = 0) -> jnp.ndarray:
    """Temperature / top-k sampling over (..., V) logits -> int32 ids.

    ``top_k`` <= 0 disables the top-k filter; ``temperature`` <= 0 is
    argmax (the greedy limit)."""
    lf = logits.astype(jnp.float32)
    if temperature <= 0.0:
        return jnp.argmax(lf, axis=-1).astype(jnp.int32)
    if top_k and top_k > 0 and top_k < lf.shape[-1]:
        kth = jax.lax.top_k(lf, top_k)[0][..., -1:]
        lf = jnp.where(lf < kth, -jnp.inf, lf)
    return jax.random.categorical(key, lf / temperature,
                                  axis=-1).astype(jnp.int32)


def logits_finite(logits: jnp.ndarray) -> jnp.ndarray:
    """(B, V) logits -> (B,) bool: True iff every logit of the row is
    finite. The per-step integrity sentinel: one device-side reduction,
    one (B,) bool transfer — the serve loop quarantines lanes whose row
    comes back False (a NaN/Inf anywhere in the row means the lane's
    cache or activations are poisoned; its argmax is garbage)."""
    return jnp.all(jnp.isfinite(logits.astype(jnp.float32)), axis=-1)


def logits_health(logits: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`logits_finite` fused with one scalar drift gauge: returns
    ``((B,) bool finite rows, () f32 max |finite logit|)`` from a single
    device-side pass. The extra scalar transfer is what feeds the
    telemetry layer's ``serve_logits_max_abs`` gauge (a slow upward creep
    is the early signal of accumulating cache corruption that the binary
    NaN sentinel only catches at the cliff); non-finite entries are
    excluded so a poisoned lane doesn't saturate the gauge."""
    lf = logits.astype(jnp.float32)
    finite = jnp.isfinite(lf)
    return (jnp.all(finite, axis=-1),
            jnp.max(jnp.where(finite, jnp.abs(lf), 0.0)))


def make_decode_step(cfg, yoco: YocoConfig = DEFAULT_YOCO,
                     rt: ModelRuntime = DEFAULT_RT, *, greedy: bool = True,
                     temperature: float = 1.0, top_k: int = 0):
    """Greedy steps keep the 4-arg signature; sampling steps take a PRNG
    key as a 5th argument (``decode_step(params, token, pos, cache, key)``)
    and draw from temperature/top-k-filtered logits."""
    def decode_logits(params, token, pos, cache):
        # ``pos``: scalar, or (B,) for heterogeneous-position batches
        return model_mod.decode_step(params, token, pos, cache,
                                     cfg, yoco, rt)

    if greedy:
        def decode_step(params, token, pos, cache):
            logits, cache = decode_logits(params, token, pos, cache)
            # covers cfg.input_kind == 'embeddings' too: next-token ids are
            # returned, the (stubbed) frontend owns the id->embedding map
            next_tok = jnp.argmax(logits, axis=-1)
            return next_tok.astype(jnp.int32), logits, cache
        return decode_step

    def decode_step_sampled(params, token, pos, cache, key):
        logits, cache = decode_logits(params, token, pos, cache)
        next_tok = sample_tokens(logits, key, temperature=temperature,
                                 top_k=top_k)
        return next_tok, logits, cache
    return decode_step_sampled


# ----------------------------------------------------------------------------
# tensor-parallel serving steps: head-parallel shard_map over a 1-D mesh
# ----------------------------------------------------------------------------
def serve_tp_specs(params, cache, tp_axis: str = 'model'):
    """(param specs, cache specs) for head-parallel serving TP: attention
    head projections shard on their last (output) dim, the paged KV pools
    on their Hkv axis (``layouts.tree_shard_specs`` — the layout registry
    owns which leaves carry a head axis); everything else — ``wo``, MLP,
    embeddings, block tables, MLA latent pools — is replicated. Both trees
    are structural templates only: specs depend on tree structure and leaf
    ranks, never on values, so an abstract (eval_shape) tree works too."""
    from repro.runtime import layouts as layouts_mod
    return (sharding.serve_tp_param_specs(params, tp_axis),
            layouts_mod.tree_shard_specs(cache, tp_axis))


def _tp_wrap(body, mesh, in_specs, out_specs):
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_tp_prefill_step(cfg, yoco: YocoConfig, mesh, params, cache, *,
                         attn_impl: str = 'einsum', tp_axis: str = 'model'):
    """Tensor-parallel twin of :func:`make_prefill_step`: the whole jit'd
    prefill body runs inside one ``shard_map`` over the ``tp_axis`` mesh
    axis. Inside the body each rank sees its own contiguous head slice of
    the projections and KV pools; ``rt.tp_reduce`` names the axis so the
    attention mix all-gathers the per-head outputs before the replicated
    ``wo`` — the ONE collective per layer of the TP serving path. Tokens,
    positions and logits are replicated (``P()``), and every rank computes
    the identical logits, so the host-side scheduler stays untouched.

    ``params``/``cache`` are structural templates for the partition specs
    (see :func:`serve_tp_specs`); ``last_pos`` is required (the continuous
    driver always passes it)."""
    rt = ModelRuntime(attn_impl=attn_impl, tp_reduce=tp_axis)
    P = jax.sharding.PartitionSpec
    pspecs, cspecs = serve_tp_specs(params, cache, tp_axis)

    def prefill_body(params, batch, cache, last_pos):
        return model_mod.prefill(params, batch, cache, cfg, yoco, rt,
                                 last_pos=last_pos)

    return _tp_wrap(prefill_body, mesh,
                    in_specs=(pspecs, P(), cspecs, P()),
                    out_specs=(P(), cspecs))


def make_tp_chunk_prefill_step(cfg, yoco: YocoConfig, mesh, params, cache,
                               *, attn_impl: str = 'einsum',
                               tp_axis: str = 'model'):
    """Tensor-parallel twin of :func:`make_chunk_prefill_step` (same
    shard_map contract as :func:`make_tp_prefill_step`)."""
    rt = ModelRuntime(attn_impl=attn_impl, tp_reduce=tp_axis)
    P = jax.sharding.PartitionSpec
    pspecs, cspecs = serve_tp_specs(params, cache, tp_axis)

    def chunk_body(params, batch, offset, limit, cache):
        return model_mod.prefill_chunk(params, batch, offset, limit, cache,
                                       cfg, yoco, rt)

    return _tp_wrap(chunk_body, mesh,
                    in_specs=(pspecs, P(), P(), P(), cspecs),
                    out_specs=(P(), cspecs))


def make_tp_decode_step(cfg, yoco: YocoConfig, mesh, params, cache, *,
                        attn_impl: str = 'einsum', tp_axis: str = 'model',
                        greedy: bool = True, temperature: float = 1.0,
                        top_k: int = 0):
    """Tensor-parallel twin of :func:`make_decode_step`: one shard_map'd
    single-token step over the head-sharded pools. Logits come out
    replicated — every rank all-gathers the same per-head attention
    outputs and runs the identical replicated ``wo``/MLP/lm_head math, so
    argmax (and temperature/top-k sampling from a replicated key) is
    bit-identical to the single-device step."""
    rt = ModelRuntime(attn_impl=attn_impl, tp_reduce=tp_axis)
    P = jax.sharding.PartitionSpec
    pspecs, cspecs = serve_tp_specs(params, cache, tp_axis)

    def decode_logits(params, token, pos, cache):
        return model_mod.decode_step(params, token, pos, cache,
                                     cfg, yoco, rt)

    if greedy:
        def decode_body(params, token, pos, cache):
            logits, cache = decode_logits(params, token, pos, cache)
            next_tok = jnp.argmax(logits, axis=-1)
            return next_tok.astype(jnp.int32), logits, cache
        return _tp_wrap(decode_body, mesh,
                        in_specs=(pspecs, P(), P(), cspecs),
                        out_specs=(P(), P(), cspecs))

    def decode_body_sampled(params, token, pos, cache, key):
        logits, cache = decode_logits(params, token, pos, cache)
        next_tok = sample_tokens(logits, key, temperature=temperature,
                                 top_k=top_k)
        return next_tok, logits, cache
    return _tp_wrap(decode_body_sampled, mesh,
                    in_specs=(pspecs, P(), P(), cspecs, P()),
                    out_specs=(P(), P(), cspecs))


def abstract_serve_state(cfg, batch: int, max_seq: int,
                         cache_dtype=jnp.bfloat16, prequant: bool = False):
    def mk(k):
        p = model_mod.init_params(k, cfg)
        if prequant:
            from repro.core import yoco_linear
            p = yoco_linear.quantize_tree(p)   # int8 weights in situ
        return p
    params = jax.eval_shape(mk, jax.random.key(0))
    cache = jax.eval_shape(
        functools.partial(model_mod.init_cache_tree, cfg, batch, max_seq,
                          cache_dtype))
    return params, cache


def serve_shardings(mesh, cfg, params_abs, cache_abs, batch: int,
                    layout: str = 'tp'):
    pspecs = sharding.param_specs(params_abs, mesh, layout)
    dp = sharding.dp_axes_of(mesh)
    cspecs = sharding.cache_specs(cache_abs, batch=batch, dp_axes=dp,
                                  mesh=mesh)
    return (sharding.to_shardings(mesh, pspecs),
            sharding.to_shardings(mesh, cspecs))


def jit_decode_step(mesh, cfg, batch: int, max_seq: int,
                    yoco: YocoConfig = DEFAULT_YOCO,
                    rt: Optional[ModelRuntime] = None, layout: str = 'tp',
                    prequant: bool = False):
    """jit'd single-token decode with sharded cache; the decode dry-run."""
    if rt is None:
        rt = ModelRuntime(mesh=mesh, dp_axes=sharding.dp_axes_of(mesh),
                          use_ep=(cfg.moe is not None
                                  and cfg.moe.impl == 'ep'),
                          act_layout='2d' if layout == 'fsdp2d' else 'batch')
    params_abs, cache_abs = abstract_serve_state(cfg, batch, max_seq,
                                                 prequant=prequant)
    psh, csh = serve_shardings(mesh, cfg, params_abs, cache_abs, batch,
                               layout)
    dp = sharding.dp_axes_of(mesh)
    import numpy as np
    dp_size = int(np.prod([mesh.shape[a] for a in dp]))
    bdim = dp if batch >= dp_size else None   # tiny-batch decode: replicate
    if cfg.input_kind in ('embeddings', 'codebooks'):
        tok_sh = sharding.to_shardings(
            mesh, jax.sharding.PartitionSpec(bdim, None))
    else:
        tok_sh = sharding.to_shardings(
            mesh, jax.sharding.PartitionSpec(bdim))
    step = make_decode_step(cfg, yoco, rt)
    return jax.jit(
        step,
        in_shardings=(psh, tok_sh, None, csh),
        out_shardings=(tok_sh if cfg.input_kind == 'tokens' else None,
                       None, csh),
        donate_argnums=(3,),
    ), (params_abs, cache_abs)


def jit_prefill_step(mesh, cfg, batch: int, seq: int, max_seq: int,
                     yoco: YocoConfig = DEFAULT_YOCO,
                     rt: Optional[ModelRuntime] = None, layout: str = 'tp',
                     prequant: bool = False):
    if rt is None:
        rt = ModelRuntime(mesh=mesh, dp_axes=sharding.dp_axes_of(mesh),
                          use_ep=(cfg.moe is not None
                                  and cfg.moe.impl == 'ep'),
                          act_layout='2d' if layout == 'fsdp2d' else 'batch')
    params_abs, cache_abs = abstract_serve_state(cfg, batch, max_seq,
                                                 prequant=prequant)
    psh, csh = serve_shardings(mesh, cfg, params_abs, cache_abs, batch,
                               layout)
    dp = sharding.dp_axes_of(mesh)
    bspecs = sharding.batch_specs(cfg, dp)
    bsh = sharding.to_shardings(mesh, dict(inputs=bspecs['inputs']))
    step = make_prefill_step(cfg, yoco, rt)
    return jax.jit(
        step,
        in_shardings=(psh, bsh, csh),
        out_shardings=(None, csh),
        donate_argnums=(2,),
    ), (params_abs, cache_abs)
