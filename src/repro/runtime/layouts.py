"""The cache-layout registry: every KV-cache layout the serving stack
knows, as one typed :class:`CacheLayout` each — the single place that is
allowed to look at a cache dict's leaves.

Before this module, the decode stack dispatched layouts by sniffing magic
dict leaves (``'bt' in cache``, ``'ks' in cache``) at every call site, and
each layout grew its own near-duplicate kernel body. The registry inverts
that: a cache dict is classified ONCE (:func:`get_layout`, by leaf
schema), and the returned layout owns everything the serving stack needs —

* **leaf schema**: which pool / table / tier leaves the layout carries
  (documented per class; ``model.init_paged_cache_tree`` builds them);
* **write ops**: where a decode token / a prefill slab lands (always the
  fp pools — tiered layouts quantize pages only as they age out);
* **gather / densify oracle**: the contiguous view the einsum reference
  attends over (tier-mixing for the quantized layouts);
* **kernel entrypoint**: which ``kernels.flash_decode`` wrapper serves the
  layout (each wrapper hands the shared ``_flash_core`` harness the
  layout's ``(index_maps, loader)`` pair);
* **quantize op** (tiered layouts): how aged-out pages move to the int8
  tier.

Tree-level helpers (:func:`with_block_tables`, :func:`quantize_tree_pages`,
and the chaos layer's :func:`scrub_tree_pages` / :func:`poison_tree_pages`)
walk a (possibly layer-stacked) cache tree, classify each dict node, and
apply the matched layout's op — ``runtime.kv_cache`` and
``runtime.kv_quant`` re-export the first two under their historical names.

Inside the layer scan every write, densify and kernel op takes the whole
layer-stacked dict plus a ``layer`` index and works on that layer in
place (:class:`CacheLayout`). On a TPU the K/V and latent rows are
stored padded to whole lanes (``kv_cache.row_width``), so that the pools
keep the row-major layout the kernels read and no step program copies
one. The padding stays behind the layouts: writes pad each row, the
kernel entrypoints pad the query and cut the padding off the output, and
the densify oracles return the rows at their logical ``width``.

Layout schemas (single layer; layer stacks prepend an (L,) dim to every
leaf):

==================  =========================================================
ContiguousLayout    ``k``/``v`` (B, S_max, Hkv, dh)
ContiguousMLALayout ``ckv`` (B, S_max, r), ``krope`` (B, S_max, d_rope)
PagedLayout         ``k``/``v`` (P, ps, Hkv, dh), ``bt`` (B, W) int32
PagedQ8Layout       PagedLayout + ``kq``/``vq`` (P, ps, Hkv, dh) int8,
                    ``ks``/``vs`` (P, Hkv) f32, ``hw`` (1,) int32
PagedMLALayout      ``cl`` (P, ps, r + d_rope), ``bt`` (B, W) int32
PagedMLAQ8Layout    PagedMLALayout + ``clq`` (P, ps, r + d_rope) int8,
                    ``cs`` (P, 1) f32, ``hw`` (1,) int32
RecurrentLayout     ``conv`` (B, W_conv-1, C), ``ssm`` (B, H, P, N) — one
                    per-slot (conv_state, ssd_state) snapshot, no
                    positional axis at all
HybridLayout        ``ssm`` (a RecurrentLayout stack) + ``attn`` (a
                    Paged/Contiguous site stack) — structural: the tree
                    walkers recurse into the members
==================  =========================================================

``bt`` rows follow the ``runtime.kv_cache`` block-table contract (page 0 =
garbage page); ``hw`` is the hot window in pages (>= 1; >= W disables the
int8 tier, bit-exact with the fp layout).

Recurrent state rides the continuous scheduler through three slot ops
instead of write/gather ops (there is no position to page behind — the
whole state is rewritten every token): **reset** (zero a slot's rows, on
admit/evict/preempt, so idle lanes decode against zeroed state and step
shapes never change), **snapshot** (a batch-1 slice, the admission
prefill's view), and **restore** (scatter the prefilled batch-1 state back
into the full-batch tree). The tree walkers :func:`reset_state_slots`,
:func:`slice_state_slot`, and :func:`merge_state_slot` apply them to
(possibly layer-stacked, possibly hybrid) cache trees;
:func:`with_block_tables` / :func:`quantize_tree_pages` pass recurrent
leaves through untouched.
"""

from __future__ import annotations

from typing import List, Optional, Tuple, Type

import jax
import jax.numpy as jnp

from repro.runtime import kv_cache as kvc
from repro.runtime import kv_quant as kvq

_REGISTRY: List[Type['CacheLayout']] = []


def _register(cls):
    """Most-specific-first registry: classes registered earlier win ties
    (the q8 layouts carry supersets of their fp twins' leaves)."""
    _REGISTRY.append(cls)
    return cls


def get_layout(cache: dict) -> Type['CacheLayout']:
    """Classify a cache dict by its leaf schema. Raises KeyError for a
    dict no registered layout matches (a malformed cache must fail loudly,
    not fall through to the wrong kernel)."""
    lay = match_layout(cache)
    if lay is None:
        raise KeyError(f'no registered cache layout matches leaves '
                       f'{sorted(cache)}; known layouts: '
                       f'{[c.name for c in _REGISTRY]}')
    return lay


def match_layout(cache: dict) -> Optional[Type['CacheLayout']]:
    """:func:`get_layout` that returns None instead of raising — the tree
    walkers use it to skip non-cache dict nodes (e.g. {'layers': ...})."""
    keys = set(cache)
    for cls in _REGISTRY:
        if cls.required <= keys:
            return cls
    return None


def dense_token_update(c: jnp.ndarray, t: jnp.ndarray, pos,
                       layer=None) -> jnp.ndarray:
    """Write the step's slab ``t`` (B, 1, ...) into a contiguous cache
    ``c`` (B, S_max, ...) at absolute position ``pos`` (scalar, or (B,)
    for heterogeneous-position batches). With an int32 ``layer``, ``c``
    is the layer-stacked (L, B, S_max, ...) cache, written at that layer
    in place."""
    t = kvc.fit_row(t, c)
    lead = () if layer is None else (layer,)
    t = t[(None,) * len(lead)]
    if jnp.ndim(pos) == 0:
        return jax.lax.dynamic_update_slice(
            c, t, lead + (0, pos) + (0,) * (c.ndim - 2 - len(lead)))

    def one(cb, tb, pb):
        return jax.lax.dynamic_update_slice(
            cb, tb, lead + (pb,) + (0,) * (cb.ndim - 1 - len(lead)))
    ax = len(lead)
    return jax.vmap(one, in_axes=(ax, ax, 0), out_axes=ax)(
        c, t, jnp.asarray(pos, jnp.int32))


def _dense_prefill(c: jnp.ndarray, t: jnp.ndarray, layer) -> jnp.ndarray:
    """Write a prompt slab ``t`` (B, S, ...) at position 0 of a contiguous
    cache (at ``layer`` of a layer-stacked one, in place)."""
    t = kvc.fit_row(t, c)
    if layer is None:
        return jax.lax.dynamic_update_slice(c, t, (0,) * c.ndim)
    return jax.lax.dynamic_update_slice(c, t[None],
                                        (layer,) + (0,) * (c.ndim - 1))


def _pos_vec(pos, b: int) -> jnp.ndarray:
    return jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))


def _latent_row(updates: dict) -> jnp.ndarray:
    """MLA write slab: ckv ‖ krope concatenated into the one-pool row the
    latent layouts store (written together, scored together)."""
    return jnp.concatenate([updates['ckv'], updates['krope']], axis=-1)


def _at(leaf: jnp.ndarray, layer) -> jnp.ndarray:
    """Layer ``layer``'s copy of a small per-layer leaf (block table, hot
    window) — the leaf itself for a single-layer cache dict."""
    return leaf if layer is None else leaf[layer]


def _rows(a: jnp.ndarray, width: Optional[int]) -> jnp.ndarray:
    """A densified view less the rows' storage padding past the logical
    ``width`` (``kv_cache.row_width``); ``a`` itself for ``width=None``."""
    return a if width is None else a[..., :width]


def _padded_query(q: jnp.ndarray, pool: jnp.ndarray) -> jnp.ndarray:
    """The query zero-padded to the pool's stored row width: the padding
    lanes of the pool hold zeros, and zero query lanes add nothing to any
    score."""
    return kvc.pad_row(q, pool.shape[-1])


def _layer_view(cache: dict, layer) -> dict:
    """One layer's slice of every leaf. Only the einsum oracle's densify
    path takes it; writes and kernels index the stacked pools in place."""
    if layer is None:
        return cache
    return {k: v[layer] for k, v in cache.items()}


# ----------------------------------------------------------------------------
# the layouts
# ----------------------------------------------------------------------------
class CacheLayout:
    """One cache layout: leaf schema + write ops + densify oracle + kernel
    entrypoint. All methods are classmethods over plain cache dicts — the
    layout carries no instance state (the cache dict IS the state).

    Every write, densify and kernel method takes an optional ``layer``
    (int32 scalar, traced inside the layer scan): the cache dict is then
    the whole layer-stacked node (every leaf has a leading (L,) dim) and
    the op reads or writes layer ``layer`` of it in place — pools at
    ``[layer, page, row]``, the small ``bt``/``hw`` copies at ``[layer]``.
    ``layer=None`` is the single-layer dict.

    The stored rows may be wider than the model's (``kv_cache.row_width``);
    the kernel entrypoints take and return the model's widths, and the
    densify oracles cut their views at ``width`` (the model's row: ``dh``,
    or ``r + d_rope`` for a latent row)."""

    name: str = ''
    required: frozenset = frozenset()   # leaf schema that identifies it
    paged: bool = False                 # carries block tables
    quantized: bool = False             # carries an int8 tier
    mla: bool = False                   # latent pool (vs K/V pools)
    recurrent: bool = False             # per-slot state, no positional axis
    composite: bool = False             # structural node; walkers recurse
    table_leaves: Tuple[str, ...] = ()  # refreshed by with_block_tables
    quant_leaves: Tuple[str, ...] = ()  # vmapped by quantize_tree_pages
    quant_probe: str = ''               # leaf whose ndim detects stacking
    quant_probe_ndim: int = 0           # single-layer ndim of quant_probe
    # integrity ops (chaos layer): per-page leaves zeroed when a
    # quarantined lane's pages are scrubbed before reallocation, and the
    # fp pools NaN-poisoning targets. Scrubbing must cover the int8
    # tiers/scales too — a poisoned page may already have quantized.
    scrub_leaves: Tuple[str, ...] = ()  # zeroed by scrub_tree_pages
    poison_leaves: Tuple[str, ...] = () # NaN'd by poison_tree_pages
    # head-parallel serving TP: leaf -> (single-layer ndim, dim carrying
    # the head axis). Leaves absent here are replicated (block tables, hot
    # windows, MLA latent pools — no head axis — and recurrent state).
    shard_dims: dict = {}

    # -- TP shard specs (serving; see tree_shard_specs) ---------------------
    @classmethod
    def shard_spec(cls, key: str, leaf, tp_axis: str = 'model'):
        """PartitionSpec for one leaf of this layout under head-parallel
        serving TP. Layer-stacked leaves are detected by rank (single-layer
        ndim + 1) — the extra leading scan dim stays unsharded."""
        from jax.sharding import PartitionSpec as P
        nd = jnp.ndim(leaf)
        spec = [None] * nd
        entry = cls.shard_dims.get(key)
        if entry is not None:
            nd_single, dim = entry
            spec[nd - nd_single + dim] = tp_axis
        return P(*spec)

    # -- write ops ----------------------------------------------------------
    @classmethod
    def write_token(cls, cache: dict, updates: dict, pos,
                    layer=None) -> dict:
        raise NotImplementedError

    @classmethod
    def write_prefill(cls, cache: dict, updates: dict, layer=None) -> dict:
        raise NotImplementedError

    @classmethod
    def write_chunk(cls, cache: dict, updates: dict, offset, limit,
                    layer=None) -> dict:
        """Write a prefill chunk's rows at absolute positions
        [offset, offset + C); rows at or beyond ``limit`` clamp onto the
        garbage page (chunked prefill never writes padding into owned —
        possibly shared — pages). Paged layouts only."""
        raise NotImplementedError(f'{cls.name} has no chunked-prefill path')

    # -- densify oracle / kernel entrypoint ---------------------------------
    @classmethod
    def gather(cls, cache: dict, pos, r: Optional[int] = None, layer=None,
               width: Optional[int] = None):
        """Contiguous views for the einsum oracle: (k, v) for K/V layouts,
        (ckv, krope) for MLA layouts (``r`` is the static latent rank the
        paged latent pool splits at). ``pos`` only matters to the tiered
        layouts (hotness)."""
        raise NotImplementedError

    @classmethod
    def gather_fp(cls, cache: dict, r: Optional[int] = None, layer=None,
                  width: Optional[int] = None):
        """Full-precision-pool densify, ignoring any int8 tier — the
        chunked-prefill read path: pages written moments ago by earlier
        chunks are not quantized yet, so tier mixing would read zeros.
        The fp pools always hold the authoritative content (quantization
        copies, never moves). Defaults to :meth:`gather` for layouts
        without a tier."""
        return cls.gather(cache, 0, r=r, layer=layer, width=width)

    @classmethod
    def flash_decode(cls, q, cache: dict, pos, *, scale, window=None,
                     interpret=None, r: Optional[int] = None, layer=None):
        """Route the decode read through this layout's Pallas kernel
        (``r`` is the static latent rank, MLA layouts only)."""
        raise NotImplementedError

    @classmethod
    def flash_chunk(cls, q, cache: dict, offset, limit, *, scale,
                    window=None, interpret=None, r: Optional[int] = None,
                    layer=None):
        """Route a chunked-prefill read (q_len > 1) through the paged
        flash kernel. Reads the fp pools only (same rationale as
        :meth:`gather_fp`). Paged layouts only."""
        raise NotImplementedError(f'{cls.name} has no chunked-prefill path')

    # -- tier ops (quantized layouts only) ----------------------------------
    @classmethod
    def quantize_pages(cls, cache: dict, pages) -> dict:
        raise NotImplementedError(
            f'{cls.name} has no int8 tier to quantize into')

    # -- slot ops (recurrent layouts only) ----------------------------------
    @classmethod
    def slot_reset(cls, cache: dict, slots) -> dict:
        raise NotImplementedError(f'{cls.name} carries no per-slot state')

    @classmethod
    def slot_snapshot(cls, cache: dict, slot: int) -> dict:
        raise NotImplementedError(f'{cls.name} carries no per-slot state')

    @classmethod
    def slot_restore(cls, cache: dict, snap: dict, slot: int) -> dict:
        raise NotImplementedError(f'{cls.name} carries no per-slot state')


class _PagedMLABase(CacheLayout):
    """Write ops and fp-pool reads shared by the two paged latent
    layouts: every write lands in the fp ``cl`` pool."""
    paged = True
    mla = True
    table_leaves = ('bt',)

    @classmethod
    def write_token(cls, cache, updates, pos, layer=None):
        lat = _latent_row(updates)
        posv = _pos_vec(pos, lat.shape[0])
        return dict(cache, cl=kvc.paged_token_update(
            cache['cl'], lat, posv, _at(cache['bt'], layer), layer))

    @classmethod
    def write_prefill(cls, cache, updates, layer=None):
        return dict(cache, cl=kvc.paged_prefill_update(
            cache['cl'], _latent_row(updates), _at(cache['bt'], layer),
            layer))

    @classmethod
    def write_chunk(cls, cache, updates, offset, limit, layer=None):
        return dict(cache, cl=kvc.paged_chunk_update(
            cache['cl'], _latent_row(updates), offset, limit,
            _at(cache['bt'], layer), layer))

    @classmethod
    def gather_fp(cls, cache, r=None, layer=None, width=None):
        assert r is not None, 'MLA gathers need the static latent rank r'
        c = _layer_view(cache, layer)
        dense = _rows(kvc.gather_pages(c['cl'], c['bt']), width)
        return dense[..., :r], dense[..., r:]

    @classmethod
    def flash_chunk(cls, q, cache, offset, limit, *, scale, window=None,
                    interpret=None, r=None, layer=None):
        # fp pool only: earlier chunks' pages are not quantized yet
        from repro.kernels import flash_decode as fd
        return fd.flash_chunk_paged_mla(_padded_query(q, cache['cl']),
                                        cache['cl'], offset, limit,
                                        _at(cache['bt'], layer), r=r,
                                        scale=scale, window=window,
                                        interpret=interpret, layer=layer)


@_register
class PagedMLAQ8Layout(_PagedMLABase):
    """Paged MLA latent pool + int8 cold tier: ``cl``/``clq``/``cs``/
    ``bt``/``hw``. Writes land in the fp ``cl`` pool; aged-out pages are
    quantized per-page absmax *before* the W_uk/W_uv expansion (see
    ``runtime.kv_quant`` for the error model)."""
    name = 'paged_mla_q8'
    required = frozenset({'cl', 'clq', 'cs', 'bt', 'hw'})
    quantized = True
    quant_leaves = ('cl', 'clq', 'cs')
    quant_probe = 'cs'
    quant_probe_ndim = 2
    scrub_leaves = ('cl', 'clq', 'cs')
    poison_leaves = ('cl',)

    @classmethod
    def gather(cls, cache, pos, r=None, layer=None, width=None):
        assert r is not None, 'MLA gathers need the static latent rank r'
        c = _layer_view(cache, layer)
        dense = _rows(kvq.dequant_gather_mla(
            c, _pos_vec(pos, c['bt'].shape[0])), width)
        return dense[..., :r], dense[..., r:]

    @classmethod
    def flash_decode(cls, q, cache, pos, *, scale, window=None,
                     interpret=None, r=None, layer=None):
        from repro.kernels import flash_decode as fd
        return fd.flash_decode_paged_mla_q8(
            _padded_query(q, cache['cl']), cache['cl'], cache['clq'],
            cache['cs'], pos,
            _at(cache['bt'], layer), _at(cache['hw'], layer), r=r,
            scale=scale, window=window, interpret=interpret, layer=layer)

    @classmethod
    def quantize_pages(cls, cache, pages):
        return kvq.quantize_latent_pages_layer(cache, pages)


@_register
class PagedMLALayout(_PagedMLABase):
    """Paged MLA latent pool: one ``cl`` pool (ckv ‖ krope per row) +
    shared ``bt`` block tables. fp-only; the q8 twin adds the cold tier."""
    name = 'paged_mla'
    required = frozenset({'cl', 'bt'})
    scrub_leaves = ('cl',)
    poison_leaves = ('cl',)

    @classmethod
    def gather(cls, cache, pos, r=None, layer=None, width=None):
        del pos
        return cls.gather_fp(cache, r=r, layer=layer, width=width)

    @classmethod
    def flash_decode(cls, q, cache, pos, *, scale, window=None,
                     interpret=None, r=None, layer=None):
        from repro.kernels import flash_decode as fd
        return fd.flash_decode_paged_mla(_padded_query(q, cache['cl']),
                                         cache['cl'], pos,
                                         _at(cache['bt'], layer), r=r,
                                         scale=scale, window=window,
                                         interpret=interpret, layer=layer)


class _PagedGQABase(CacheLayout):
    """Write ops and fp-pool reads shared by the two paged GQA layouts:
    every write lands in the fp ``k``/``v`` pools."""
    paged = True
    table_leaves = ('bt',)

    @classmethod
    def write_token(cls, cache, updates, pos, layer=None):
        posv = _pos_vec(pos, updates['k'].shape[0])
        bt = _at(cache['bt'], layer)
        return dict(
            cache,
            k=kvc.paged_token_update(cache['k'], updates['k'], posv, bt,
                                     layer),
            v=kvc.paged_token_update(cache['v'], updates['v'], posv, bt,
                                     layer))

    @classmethod
    def write_prefill(cls, cache, updates, layer=None):
        bt = _at(cache['bt'], layer)
        return dict(
            cache,
            k=kvc.paged_prefill_update(cache['k'], updates['k'], bt, layer),
            v=kvc.paged_prefill_update(cache['v'], updates['v'], bt, layer))

    @classmethod
    def write_chunk(cls, cache, updates, offset, limit, layer=None):
        bt = _at(cache['bt'], layer)
        return dict(
            cache,
            k=kvc.paged_chunk_update(cache['k'], updates['k'], offset,
                                     limit, bt, layer),
            v=kvc.paged_chunk_update(cache['v'], updates['v'], offset,
                                     limit, bt, layer))

    @classmethod
    def gather_fp(cls, cache, r=None, layer=None, width=None):
        del r
        c = _layer_view(cache, layer)
        return (_rows(kvc.gather_pages(c['k'], c['bt']), width),
                _rows(kvc.gather_pages(c['v'], c['bt']), width))

    @classmethod
    def flash_chunk(cls, q, cache, offset, limit, *, scale, window=None,
                    interpret=None, r=None, layer=None):
        # fp pools only: earlier chunks' pages are not quantized yet
        del r
        from repro.kernels import flash_decode as fd
        out = fd.flash_chunk_paged(_padded_query(q, cache['k']), cache['k'],
                                   cache['v'], offset, limit,
                                   _at(cache['bt'], layer), scale=scale,
                                   window=window, interpret=interpret,
                                   layer=layer)
        return out[..., :q.shape[-1]]


@_register
class PagedQ8Layout(_PagedGQABase):
    """Paged GQA pools + int8 cold tier: ``k``/``v``/``kq``/``vq``/``ks``/
    ``vs``/``bt``/``hw``. Writes land in the fp pools; aged-out pages are
    quantized per-page, per-head absmax."""
    name = 'paged_q8'
    required = frozenset({'k', 'v', 'kq', 'vq', 'ks', 'vs', 'bt', 'hw'})
    quantized = True
    quant_leaves = ('k', 'v', 'kq', 'vq', 'ks', 'vs')
    quant_probe = 'ks'
    quant_probe_ndim = 2
    scrub_leaves = ('k', 'v', 'kq', 'vq', 'ks', 'vs')
    poison_leaves = ('k', 'v')
    # pools split the Hkv axis; the per-page per-head scales follow it
    shard_dims = {'k': (4, 2), 'v': (4, 2), 'kq': (4, 2), 'vq': (4, 2),
                  'ks': (2, 1), 'vs': (2, 1)}

    @classmethod
    def gather(cls, cache, pos, r=None, layer=None, width=None):
        del r
        c = _layer_view(cache, layer)
        return tuple(_rows(a, width) for a in kvq.dequant_gather(
            c, _pos_vec(pos, c['bt'].shape[0])))

    @classmethod
    def flash_decode(cls, q, cache, pos, *, scale, window=None,
                     interpret=None, r=None, layer=None):
        del r
        from repro.kernels import flash_decode as fd
        out = fd.flash_decode_paged_q8(
            _padded_query(q, cache['k']), cache['k'], cache['v'],
            cache['kq'], cache['vq'], cache['ks'], cache['vs'], pos,
            _at(cache['bt'], layer), _at(cache['hw'], layer), scale=scale,
            window=window, interpret=interpret, layer=layer)
        return out[..., :q.shape[-1]]

    @classmethod
    def quantize_pages(cls, cache, pages):
        return kvq.quantize_pages_layer(cache, pages)


@_register
class PagedLayout(_PagedGQABase):
    """Paged GQA pools: ``k``/``v`` pools + shared ``bt`` block tables."""
    name = 'paged'
    required = frozenset({'k', 'v', 'bt'})
    scrub_leaves = ('k', 'v')
    poison_leaves = ('k', 'v')
    shard_dims = {'k': (4, 2), 'v': (4, 2)}     # (P, ps, Hkv, dh): split Hkv

    @classmethod
    def gather(cls, cache, pos, r=None, layer=None, width=None):
        del pos
        return cls.gather_fp(cache, r=r, layer=layer, width=width)

    @classmethod
    def flash_decode(cls, q, cache, pos, *, scale, window=None,
                     interpret=None, r=None, layer=None):
        del r
        from repro.kernels import flash_decode as fd
        out = fd.flash_decode_paged(_padded_query(q, cache['k']), cache['k'],
                                    cache['v'], pos, _at(cache['bt'], layer),
                                    scale=scale, window=window,
                                    interpret=interpret, layer=layer)
        return out[..., :q.shape[-1]]


@_register
class ContiguousMLALayout(CacheLayout):
    """Contiguous MLA latent cache: ``ckv``/``krope`` (B, S_max, ·). The
    einsum-only decode layout (the MLA flash kernels are paged — serve
    long contexts through ``--continuous``)."""
    name = 'contiguous_mla'
    required = frozenset({'ckv', 'krope'})
    mla = True

    @classmethod
    def write_token(cls, cache, updates, pos, layer=None):
        return dict(cache,
                    ckv=dense_token_update(cache['ckv'], updates['ckv'],
                                           pos, layer),
                    krope=dense_token_update(cache['krope'],
                                             updates['krope'], pos, layer))

    @classmethod
    def write_prefill(cls, cache, updates, layer=None):
        return dict(
            cache,
            ckv=_dense_prefill(cache['ckv'], updates['ckv'], layer),
            krope=_dense_prefill(cache['krope'], updates['krope'], layer))

    @classmethod
    def gather(cls, cache, pos, r=None, layer=None, width=None):
        del pos, r, width           # ckv and krope are stored unpadded
        c = _layer_view(cache, layer)
        return c['ckv'], c['krope']


@_register
class ContiguousLayout(CacheLayout):
    """Contiguous GQA cache: ``k``/``v`` (B, S_max, Hkv, dh)."""
    name = 'contiguous'
    required = frozenset({'k', 'v'})
    shard_dims = {'k': (4, 2), 'v': (4, 2)}     # (B, S, Hkv, dh): split Hkv

    @classmethod
    def write_token(cls, cache, updates, pos, layer=None):
        return dict(cache,
                    k=dense_token_update(cache['k'], updates['k'], pos,
                                         layer),
                    v=dense_token_update(cache['v'], updates['v'], pos,
                                         layer))

    @classmethod
    def write_prefill(cls, cache, updates, layer=None):
        return dict(cache,
                    k=_dense_prefill(cache['k'], updates['k'], layer),
                    v=_dense_prefill(cache['v'], updates['v'], layer))

    @classmethod
    def gather(cls, cache, pos, r=None, layer=None, width=None):
        del pos, r
        c = _layer_view(cache, layer)
        return _rows(c['k'], width), _rows(c['v'], width)

    @classmethod
    def flash_decode(cls, q, cache, pos, *, scale, window=None,
                     interpret=None, r=None, layer=None):
        del r
        from repro.kernels import flash_decode as fd
        out = fd.flash_decode(_padded_query(q, cache['k']), cache['k'],
                              cache['v'], pos, scale=scale, window=window,
                              interpret=interpret, layer=layer)
        return out[..., :q.shape[-1]]


@_register
class RecurrentLayout(CacheLayout):
    """Per-slot recurrent state: ``conv`` (B, W_conv-1, C) + ``ssm``
    (B, H, P, N). No positional axis — the whole state is rewritten every
    token — so instead of write/gather ops the layout exposes the three
    slot ops the continuous scheduler needs (reset / snapshot / restore;
    see the module docstring). The ops delegate to the pure helpers in
    ``models.ssm`` and handle both single trees and (L,)-stacked ones by
    probing ``conv``'s ndim."""
    name = 'recurrent'
    required = frozenset({'conv', 'ssm'})
    recurrent = True
    state_leaves = ('conv', 'ssm')
    state_probe = 'conv'
    state_probe_ndim = 3        # (B, W_conv-1, C); stacks prepend (L,)

    @classmethod
    def _axis(cls, cache: dict) -> int:
        return cache[cls.state_probe].ndim - cls.state_probe_ndim

    @classmethod
    def slot_reset(cls, cache, slots):
        from repro.models import ssm as ssm_mod
        return ssm_mod.slot_reset(cache, slots, axis=cls._axis(cache))

    @classmethod
    def slot_snapshot(cls, cache, slot):
        from repro.models import ssm as ssm_mod
        return ssm_mod.slot_snapshot(cache, slot, axis=cls._axis(cache))

    @classmethod
    def slot_restore(cls, cache, snap, slot):
        from repro.models import ssm as ssm_mod
        return ssm_mod.slot_restore(cache, snap, slot,
                                    axis=cls._axis(cache))


@_register
class HybridLayout(CacheLayout):
    """Structural marker for hybrid (attention + SSM) cache trees:
    ``ssm`` (a RecurrentLayout stack) + ``attn`` (a paged/contiguous site
    stack). Carries no ops of its own — the tree walkers recurse into the
    member subtrees and each inner dict classifies to its own layout."""
    name = 'hybrid'
    required = frozenset({'ssm', 'attn'})
    composite = True


# ----------------------------------------------------------------------------
# tree walkers (layer-stacked cache trees)
# ----------------------------------------------------------------------------
def with_block_tables(cache_tree, tables: jnp.ndarray, hot_window=None):
    """Refresh every paged layout's table leaves in a (possibly
    layer-stacked) cache tree with ``tables``, broadcast over each leaf's
    leading layer dim. The scheduler calls this each time admissions /
    evictions change the tables; pools pass through by reference (no
    copy). ``hot_window`` (optional int) additionally rewrites every
    ``hw`` copy of the tiered layouts — the same broadcast discipline, so
    a retuned hot window reaches every layer's copy at once."""
    tables = jnp.asarray(tables, jnp.int32)

    def walk(node):
        if isinstance(node, dict):
            lay = match_layout(node)
            out = {}
            for key, val in node.items():
                if lay is not None and key in lay.table_leaves:
                    out[key] = jnp.broadcast_to(
                        tables[None], (val.shape[0],) + tables.shape)
                elif (lay is not None and lay.quantized and key == 'hw'
                        and hot_window is not None):
                    out[key] = jnp.broadcast_to(
                        jnp.asarray([hot_window], jnp.int32)[None],
                        (val.shape[0], 1))
                else:
                    out[key] = walk(val)
            return out
        return node

    return walk(cache_tree)


def quantize_tree_pages(cache_tree, pages: jnp.ndarray):
    """Apply each quantized layout's :meth:`~CacheLayout.quantize_pages`
    to every matching dict node of a (possibly layer-stacked) cache tree.
    Page indices are physical, so one vector covers every layer.
    Non-quantized subtrees pass through untouched."""
    pages = jnp.asarray(pages, jnp.int32).reshape(-1)

    def quant_stack(lay, node):
        keys = lay.quant_leaves
        if node[lay.quant_probe].ndim == lay.quant_probe_ndim:
            return lay.quantize_pages(node, pages)   # single layer dict

        def one(*leaves):
            d = lay.quantize_pages(dict(zip(keys, leaves)), pages)
            return tuple(d[k] for k in keys)

        stacked = jax.vmap(one)(*(node[k] for k in keys))
        return dict(node, **dict(zip(keys, stacked)))

    def walk(node):
        if isinstance(node, dict):
            lay = match_layout(node)
            if lay is not None and lay.quantized:
                return quant_stack(lay, node)
            return {k: walk(v) for k, v in node.items()}
        return node

    return walk(cache_tree)


def _page_indexed_update(node, lay, leaves, pages, value):
    """Write ``value`` into the page rows of the named per-page leaves of
    one paged dict node, handling layer-stacked leaves (leading (L,) dim,
    detected off the table leaf: (B, W) single vs (L, B, W) stacked)."""
    stacked = node[lay.table_leaves[0]].ndim == 3
    out = dict(node)
    for key in leaves:
        leaf = node[key]
        if stacked:
            out[key] = leaf.at[:, pages].set(value)
        else:
            out[key] = leaf.at[pages].set(value)
    return out


def scrub_tree_pages(cache_tree, pages: jnp.ndarray):
    """Zero the given physical pages in EVERY per-page leaf (fp pools,
    int8 tiers, scales) of every paged node — the quarantine path: a lane
    whose logits went non-finite is released and its pages must be
    scrubbed before the free list can hand them to another request, or
    the poison leaks to the next tenant (NaN in a masked cache row still
    propagates through the additive mask: NaN + -inf = NaN). Page indices
    are physical, so one vector covers every layer; padding with the
    garbage page 0 is harmless. Non-paged subtrees pass through."""
    pages = jnp.asarray(pages, jnp.int32).reshape(-1)

    def walk(node):
        if isinstance(node, dict):
            lay = match_layout(node)
            if lay is not None and lay.scrub_leaves:
                return _page_indexed_update(node, lay, lay.scrub_leaves,
                                            pages, 0)
            return {k: walk(v) for k, v in node.items()}
        return node

    return walk(cache_tree)


def copy_tree_pages(cache_tree, src: int, dst: int):
    """Copy ONE physical page's content ``src`` -> ``dst`` in every
    per-page leaf (fp pools, int8 tiers, scales) of every paged node —
    the copy-on-write split: a request that matched a full cached prefix
    gets a private copy of the boundary page before its first write, so
    the shared original is never mutated. Copying the int8 tier and
    scales too keeps the new owner's tier state consistent if the source
    page had already aged out and quantized."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)

    def walk(node):
        if isinstance(node, dict):
            lay = match_layout(node)
            if lay is not None and lay.scrub_leaves:
                stacked = node[lay.table_leaves[0]].ndim == 3
                out = dict(node)
                for key in lay.scrub_leaves:
                    leaf = node[key]
                    if stacked:
                        out[key] = leaf.at[:, dst].set(leaf[:, src])
                    else:
                        out[key] = leaf.at[dst].set(leaf[src])
                return out
            return {k: walk(v) for k, v in node.items()}
        return node

    return walk(cache_tree)


def zero_tree_tail(cache_tree, table_row: jnp.ndarray, start: int,
                   stop: int):
    """Zero the logical rows [start, stop) of one request's pages in
    every paged node's fp pools, following its block-table row
    ``table_row`` (W,). The monolithic prefill pads prompts to a page
    multiple and writes the padded tail rows into owned pages; with
    prefix sharing those rows become publishable (sealed) state, so the
    driver zeroes them right after prefill. Rows outside [start, stop)
    redirect onto the garbage page 0 (never read), so the update is a
    single static-shape scatter."""
    table_row = jnp.asarray(table_row, jnp.int32).reshape(-1)
    start = jnp.asarray(start, jnp.int32)   # traced: one jit shape covers
    stop = jnp.asarray(stop, jnp.int32)     # every (plen, blocks) pair

    def zero_node(lay, node):
        stacked = node[lay.table_leaves[0]].ndim == 3
        out = dict(node)
        for key in lay.poison_leaves:
            pool = node[key]
            ps = pool.shape[2] if stacked else pool.shape[1]
            w = table_row.shape[0]
            logical = jnp.arange(w * ps, dtype=jnp.int32)
            live = (logical >= start) & (logical < stop)
            page = jnp.where(live, table_row[logical // ps],
                             kvc.GARBAGE_PAGE)
            row = logical % ps
            if stacked:
                out[key] = pool.at[:, page, row].set(0)
            else:
                out[key] = pool.at[page, row].set(0)
        return out

    def walk(node):
        if isinstance(node, dict):
            lay = match_layout(node)
            if lay is not None and lay.paged and lay.poison_leaves:
                return zero_node(lay, node)
            return {k: walk(v) for k, v in node.items()}
        return node

    return walk(cache_tree)


def poison_tree_pages(cache_tree, pages: jnp.ndarray, value=float('nan')):
    """Write ``value`` (default NaN) into the given physical pages of
    every paged node's fp pools — the chaos layer's model of a corrupted
    in-memory tier read. Only the fp ``poison_leaves`` are touched (an
    int8 tier cannot represent NaN; the analog-error story for the cold
    tier lives in the IMA error model instead)."""
    pages = jnp.asarray(pages, jnp.int32).reshape(-1)

    def walk(node):
        if isinstance(node, dict):
            lay = match_layout(node)
            if lay is not None and lay.poison_leaves:
                return _page_indexed_update(node, lay, lay.poison_leaves,
                                            pages, value)
            return {k: walk(v) for k, v in node.items()}
        return node

    return walk(cache_tree)


def reset_state_slots(cache_tree, slots):
    """Zero the given batch slots of every recurrent node in a (possibly
    layer-stacked, possibly hybrid) cache tree. The scheduler calls this
    on admit (a fresh request must not see the evicted tenant's state) and
    on evict/preempt (idle lanes decode against zeroed state, keeping step
    shapes constant). Non-recurrent subtrees pass through by reference."""
    def walk(node):
        if isinstance(node, dict):
            lay = match_layout(node)
            if lay is not None and lay.recurrent:
                return lay.slot_reset(node, slots)
            return {k: walk(v) for k, v in node.items()}
        return node

    return walk(cache_tree)


def slice_state_slot(cache_tree, slot: int):
    """Batch-1 view of one slot's recurrent state — the admission
    prefill's cache tree. Recurrent leaves are sliced to ``slot:slot+1``
    (a copy, so the full tree's rows survive a donated prefill);
    everything else (paged pools, tables) passes through by reference. On
    an attention-only tree this is the identity walk."""
    def walk(node):
        if isinstance(node, dict):
            lay = match_layout(node)
            if lay is not None and lay.recurrent:
                return lay.slot_snapshot(node, slot)
            return {k: walk(v) for k, v in node.items()}
        return node

    return walk(cache_tree)


def merge_state_slot(full_tree, part_tree, slot: int):
    """Fold an admission prefill's batch-1 tree back into the full-batch
    tree: recurrent nodes scatter the part's row into the full tree's
    (never-donated) leaves; every other node takes the part's value —
    paged pools pass through :func:`slice_state_slot` by reference, so
    the prefilled pool buffers ARE the part's leaves after donation."""
    def walk(full, part):
        if isinstance(full, dict) and isinstance(part, dict):
            lay = match_layout(full)
            if lay is not None and lay.recurrent:
                return lay.slot_restore(full, part, slot)
            return {k: walk(full[k], part[k]) for k in full}
        return part

    return walk(full_tree, part_tree)


def tree_shard_specs(cache_tree, tp_axis: str = 'model'):
    """PartitionSpec pytree for a (possibly layer-stacked) cache tree under
    head-parallel serving TP: each dict node classifies to its layout and
    each leaf gets that layout's :meth:`~CacheLayout.shard_spec` — GQA
    pools (and their int8 tiers + per-head scales) split the Hkv axis, MLA
    latent pools / block tables / hot windows / recurrent state replicate.
    Keeping the routing here means the tree walkers above stay layout-
    driven when fed sharded pools: they are plain jit'd pytree ops, so
    GSPMD propagates these shardings through them unchanged."""
    from jax.sharding import PartitionSpec as P

    def walk(node):
        if isinstance(node, dict):
            lay = match_layout(node)
            if lay is not None and not lay.composite:
                return {k: lay.shard_spec(k, v, tp_axis)
                        for k, v in node.items()}
            return {k: walk(v) for k, v in node.items()}
        return P(*([None] * jnp.ndim(node)))

    return walk(cache_tree)
