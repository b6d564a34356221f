"""Mixture-of-Experts FFN: token-choice top-k router with two executions.

``dense``  — exact combine: every expert runs on every token, outputs mixed
             by router weights. O(E·T) compute: the *oracle* path used in
             smoke tests and as the correctness reference for the EP path.
``ep``     — production expert parallelism: capacity-buffered sort-based
             dispatch + ``all_to_all`` across the mesh's 'model' axis inside
             ``jax.shard_map``. Tokens enter sharded over (dp, model)
             [sequence-parallel], experts live sharded over 'model'.
             This is the layout where the EP all_to_all is the row-driver
             broadcast analogue of the paper (inputs move to stationary
             weights, partial results return once).

Router: softmax top-k (optionally normalized), with the standard
load-balancing auxiliary loss (Switch/DeepSeek style) returned as metrics.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import yoco_linear
from repro.core.yoco_linear import YocoConfig
from repro.models.layers import dense_init


# ----------------------------------------------------------------------------
# init
# ----------------------------------------------------------------------------
def init_moe(key: jax.Array, cfg) -> dict:
    """Expert weights stacked (E, ...) for vectorized/sharded execution.
    Stacks are padded to ``moe.stack_size`` (zero dummy experts the router
    never addresses) so EP sharding divides evenly without in-step
    resharding."""
    mo = cfg.moe
    d = cfg.d_model
    wide = cfg.mlp_type in ('swiglu', 'geglu')
    k1, k2, k3, k4, k5, k6, k7 = jax.random.split(key, 7)
    e = mo.stack_size                 # padded stacks; router stays unpadded
    f = mo.d_ff_expert
    p = dict(router=dense_init(k1, d, mo.n_experts, scale=0.02))
    if wide:
        p['w_gate'] = jax.random.normal(k2, (e, d, f)) / jnp.sqrt(d)
        p['w_up'] = jax.random.normal(k3, (e, d, f)) / jnp.sqrt(d)
        p['w_down'] = jax.random.normal(k4, (e, f, d)) / jnp.sqrt(f)
    else:
        p['w_in'] = jax.random.normal(k2, (e, d, f)) / jnp.sqrt(d)
        p['w_out'] = jax.random.normal(k3, (e, f, d)) / jnp.sqrt(f)
    if mo.d_ff_shared:
        fs = mo.d_ff_shared
        if wide:
            p['sh_gate'] = dense_init(k5, d, fs)
            p['sh_up'] = dense_init(k6, d, fs)
            p['sh_down'] = dense_init(k7, fs, d)
        else:
            p['sh_in'] = dense_init(k5, d, fs)
            p['sh_out'] = dense_init(k6, fs, d)
    return p


def _act(cfg):
    if cfg.mlp_type == 'swiglu':
        return jax.nn.silu
    return lambda t: jax.nn.gelu(t, approximate=True)


def _expert_ffn(p: dict, x: jnp.ndarray, cfg) -> jnp.ndarray:
    """x: (E, C, d) through per-expert weights (E, d, f)/(E, f, d)."""
    act = _act(cfg)
    if 'w_gate' in p:
        g = jnp.einsum('ecd,edf->ecf', x, p['w_gate'].astype(x.dtype))
        u = jnp.einsum('ecd,edf->ecf', x, p['w_up'].astype(x.dtype))
        return jnp.einsum('ecf,efd->ecd', act(g) * u,
                          p['w_down'].astype(x.dtype))
    h = act(jnp.einsum('ecd,edf->ecf', x, p['w_in'].astype(x.dtype)))
    return jnp.einsum('ecf,efd->ecd', h, p['w_out'].astype(x.dtype))


def _shared_ffn(p: dict, x: jnp.ndarray, cfg, yoco: YocoConfig) -> jnp.ndarray:
    act = _act(cfg)
    if 'sh_gate' in p:
        g = yoco_linear.linear(x, p['sh_gate'], cfg=yoco)
        u = yoco_linear.linear(x, p['sh_up'], cfg=yoco)
        return yoco_linear.linear(act(g) * u, p['sh_down'], cfg=yoco)
    h = act(yoco_linear.linear(x, p['sh_in'], cfg=yoco))
    return yoco_linear.linear(h, p['sh_out'], cfg=yoco)


# ----------------------------------------------------------------------------
# router
# ----------------------------------------------------------------------------
def route(p: dict, x: jnp.ndarray, cfg) -> Tuple[jnp.ndarray, jnp.ndarray, dict]:
    """x: (T, d) -> (gates (T, k), expert_ids (T, k) int32, aux metrics)."""
    mo = cfg.moe
    logits = (x.astype(jnp.float32) @ p['router'].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                 # (T, E)
    gates, ids = jax.lax.top_k(probs, mo.top_k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)  # renormalize
    # Switch-style load-balance loss: E * sum_e f_e * p_e
    e = mo.n_experts
    me = jnp.mean(probs, axis=0)                            # mean router prob
    onehot = jax.nn.one_hot(ids[:, 0], e)                   # top-1 assignment
    ce = jnp.mean(onehot, axis=0)                           # fraction routed
    aux = e * jnp.sum(me * ce)
    return gates.astype(x.dtype), ids.astype(jnp.int32), dict(
        aux_loss=aux, router_entropy=-jnp.mean(
            jnp.sum(probs * jnp.log(probs + 1e-9), axis=-1)))


# ----------------------------------------------------------------------------
# dense (oracle) execution
# ----------------------------------------------------------------------------
def moe_dense(p: dict, x: jnp.ndarray, cfg, yoco: YocoConfig,
              ) -> Tuple[jnp.ndarray, dict]:
    """Exact combine; no capacity drops. x: (B, S, d)."""
    mo = cfg.moe
    b, s, d = x.shape
    xt = x.reshape(-1, d)
    gates, ids, metrics = route(p, xt, cfg)
    # run every expert on every token: (E, T, d)
    xe = jnp.broadcast_to(xt[None], (mo.stack_size,) + xt.shape)
    ye = _expert_ffn(p, xe, cfg)                            # (E, T, d)
    mix = jnp.zeros((xt.shape[0], mo.stack_size), x.dtype)
    mix = mix.at[jnp.arange(xt.shape[0])[:, None], ids].add(gates)
    y = jnp.einsum('te,etd->td', mix, ye)
    if mo.d_ff_shared:
        y = y + _shared_ffn(p, xt, cfg, yoco)
    return y.reshape(b, s, d), metrics


# ----------------------------------------------------------------------------
# sort-based capacity dispatch (shared by ep path and its single-host tests)
# ----------------------------------------------------------------------------
def _positions_in_expert(flat_ids: jnp.ndarray, n_experts: int) -> jnp.ndarray:
    """For each routing slot, its arrival index within its expert's queue.
    O(T·k log) time, O(T·k) memory (no (T, E) one-hots)."""
    tk = flat_ids.shape[0]
    order = jnp.argsort(flat_ids, stable=True)
    sorted_ids = flat_ids[order]
    ar = jnp.arange(tk, dtype=jnp.int32)
    is_start = jnp.concatenate([jnp.ones((1,), bool),
                                sorted_ids[1:] != sorted_ids[:-1]])
    starts = jnp.where(is_start, ar, 0)
    starts = jax.lax.associative_scan(jnp.maximum, starts)
    pos_sorted = ar - starts
    return jnp.zeros((tk,), jnp.int32).at[order].set(pos_sorted)


def dispatch_combine(p: dict, xt: jnp.ndarray, cfg, yoco: YocoConfig,
                     capacity: int, expert_fn=None,
                     n_buckets: Optional[int] = None
                     ) -> Tuple[jnp.ndarray, dict]:
    """Capacity-buffered MoE on (T, d) tokens against the *local* expert
    stack in ``p``. ``expert_fn(buf (E', C, d)) -> (E', C, d)`` defaults to
    the local FFN; the EP path passes a wrapper that all_to_alls around it.
    ``n_buckets`` >= n_experts pads the dispatch buffer (EP divisibility)."""
    mo = cfg.moe
    nb = n_buckets or mo.n_experts
    t, d = xt.shape
    k = mo.top_k
    gates, ids, metrics = route(p, xt, cfg)
    flat_ids = ids.reshape(-1)                              # (T*k,)
    pos = _positions_in_expert(flat_ids, mo.n_experts)      # (T*k,)
    keep = pos < capacity
    dest = jnp.where(keep, flat_ids * capacity + pos,
                     nb * capacity)                         # OOB -> dropped
    x_rep = jnp.repeat(xt, k, axis=0)                       # (T*k, d)
    buf = jnp.zeros((nb * capacity, d), xt.dtype)
    buf = buf.at[dest].set(x_rep, mode='drop')
    buf = buf.reshape(nb, capacity, d)
    y_buf = (expert_fn or (lambda bb: _expert_ffn(p, bb, cfg)))(buf)
    y_flat = y_buf.reshape(-1, d)
    y_rep = jnp.where(keep[:, None],
                      y_flat.at[jnp.clip(dest, 0, nb * capacity - 1)]
                      .get(mode='clip'), 0.0)
    y = (y_rep.reshape(t, k, d)
         * gates[..., None].astype(y_rep.dtype)).sum(axis=1)
    metrics['drop_fraction'] = 1.0 - jnp.mean(keep.astype(jnp.float32))
    if mo.d_ff_shared:
        y = y + _shared_ffn(p, xt, cfg, yoco).astype(y.dtype)
    return y.astype(xt.dtype), metrics


# ----------------------------------------------------------------------------
# expert-parallel execution (shard_map + all_to_all over 'model')
# ----------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class EPContext:
    mesh: object                  # jax.sharding.Mesh
    dp_axes: tuple                # e.g. ('data',) or ('pod', 'data')
    ep_axis: str = 'model'


def moe_ep(p: dict, x: jnp.ndarray, cfg, yoco: YocoConfig, ctx: EPContext,
           ) -> Tuple[jnp.ndarray, dict]:
    """Expert-parallel MoE. x: (B, S, d) sharded P(dp_axes, ep_axis, None) —
    sequence-parallel entry (jit reshards automatically when the caller holds
    activations replicated over 'model').

    Per (dp, ep) shard: route local tokens; build the (E_pad, C, d) dispatch
    buffer; all_to_all over the EP axis so each rank holds its E_loc experts'
    tokens from every peer; run the local expert FFN; all_to_all back;
    combine. Expert weights are sharded (E_pad -> ep_axis)."""
    mo = cfg.moe
    ep = ctx.mesh.shape[ctx.ep_axis]
    e_pad = mo.stack_size
    assert e_pad % ep == 0, (
        f'expert stack {e_pad} must divide EP={ep}: set '
        f'MoEConfig.pad_experts_to (in-step padding would force a full '
        f'expert all-gather per layer — §Perf qwen2-moe iter 2)')
    b, s, d = x.shape
    pp = dict(p)

    # sequence-parallel entry when the seq dim can split over the EP axis;
    # decode (s == 1) keeps tokens replicated over 'model' instead — the
    # dispatch math is identical, compute is duplicated EP-ways on a tiny
    # token count (standard decode-time EP behavior)
    seq_sharded = s % ep == 0 and s > 1
    dp_size = 1
    for a in ctx.dp_axes:
        dp_size *= ctx.mesh.shape[a]
    shards = dp_size * (ep if seq_sharded else 1)
    tokens_global = b * s
    t_loc = max(tokens_global // shards, 1)
    capacity = max(int(t_loc * mo.top_k * mo.capacity_factor / mo.n_experts),
                   mo.top_k)

    ep_axis = ctx.ep_axis

    def shard_fn(pp_l, x_l):
        tl, dl = x_l.shape[0] * x_l.shape[1], x_l.shape[2]
        xt = x_l.reshape(tl, dl)

        def expert_fn(buf):                       # buf: (E_pad, C, d) local
            # send each EP peer its experts' slices; receive my experts'
            # slices from every peer -> (E_loc, ep*C, d)
            recv = jax.lax.all_to_all(buf, ep_axis, split_axis=0,
                                      concat_axis=1, tiled=True)
            y = _expert_ffn(pp_l, recv, cfg)      # local experts (E_loc,...)
            back = jax.lax.all_to_all(y, ep_axis, split_axis=1,
                                      concat_axis=0, tiled=True)
            return back

        y, m = dispatch_combine(pp_l, xt, cfg, yoco, capacity, expert_fn,
                                n_buckets=e_pad)
        m = jax.tree.map(
            lambda v: jax.lax.pmean(
                jax.lax.pmean(v, ep_axis),
                ctx.dp_axes) if jnp.ndim(v) == 0 else v, m)
        return y.reshape(x_l.shape), m

    pspecs = {}
    for kname, v in pp.items():
        if kname in ('w_gate', 'w_up', 'w_down', 'w_in', 'w_out'):
            pspecs[kname] = P(ep_axis, None, None)
        else:
            pspecs[kname] = P(*([None] * v.ndim))
    xspec = (P(ctx.dp_axes, ep_axis, None) if seq_sharded
             else P(ctx.dp_axes, None, None))

    y, metrics = jax.shard_map(
        shard_fn, mesh=ctx.mesh,
        in_specs=(pspecs, xspec),
        out_specs=(xspec, P()),
        check_vma=False,
    )(pp, x)
    return y, metrics


def moe_apply(p: dict, x: jnp.ndarray, cfg, yoco: YocoConfig,
              ctx: Optional[EPContext] = None) -> Tuple[jnp.ndarray, dict]:
    """Entry point: EP when a mesh context is supplied & requested, else
    dense oracle."""
    if ctx is not None and cfg.moe.impl == 'ep':
        return moe_ep(p, x, cfg, yoco, ctx)
    return moe_dense(p, x, cfg, yoco)
