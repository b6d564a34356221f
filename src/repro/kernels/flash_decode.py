"""Fused Pallas flash-decode attention kernels for batched serving.

One query token per request attends its whole KV cache in a single pass:
the kernel streams the cache in ``(block, Hkv, dh)`` tiles and carries the
online-softmax running max / running sum / unnormalized output in VMEM
scratch across the S grid dimension — the kernel-level analogue of the
paper's time-domain accumulation: partial results never leave the chip and
are never renormalized mid-reduction; the single output conversion
(``acc / l``) happens once, on the last tile. You Only Convert Once.

Batched serving shape: every request sits at its own absolute position, so
the kernel takes a per-request ``pos`` vector (and a per-request sliding
``window``). Keys beyond ``pos`` — cache garbage, padding, or other
requests' territory — are masked inside the tile, which is what lets one
jit'd decode step serve heterogeneous-position requests.

One composable core, five memory layouts
----------------------------------------
Every public entrypoint runs the SAME harness (:func:`_flash_core`: one
``pltpu.PrefetchScalarGridSpec`` ``pallas_call`` + one kernel body) and the
SAME compute path (:func:`_softmax_tile`, the only online-softmax body in
this module). What differs per layout is a ``(index_maps, loader)`` pair:

* **index maps** decide which physical tile each grid step DMAs. Dead
  steps (fully-masked tiles) clamp their block index onto the nearest live
  block — Pallas' pipeline emitter skips the DMA when the block index
  repeats, so dead tiles generate no HBM traffic. Tiered layouts route
  each step's DMA to exactly one tier (the untaken tier's map clamps onto
  the garbage page, repeated index, DMA elided).
* the **loader** turns the fetched refs into f32 head-major
  ``(Hkv, bs, dh)`` K/V tiles — a plain read for fp layouts, an in-VMEM
  dequantization (rounded through the serving dtype so kernel and einsum
  oracle agree to f32 roundoff) for the int8 tiers, a fetch-once/use-twice
  split for the MLA latent pool.

The pairs are what ``runtime/layouts.py``'s :class:`CacheLayout` registry
hands out — each cache layout owns its kernel entrypoint here, and nothing
else in the serving stack needs to know which leaves a layout carries.

Layout family notes:

* ``flash_decode`` (contiguous): ``impl='prefetch'`` (default) uses
  data-dependent index maps; ``impl='streamed'`` (legacy benchmark
  baseline) uses identity index maps — every tile is still DMA'd, masked
  tiles only skip compute. Same harness, same body, bitwise-equal outputs.
* ``flash_decode_paged``: the per-request block table (a third
  scalar-prefetch operand) maps logical key blocks to physical pages of a
  pool ``(num_pages, page_size, Hkv, dh)`` shared by all requests. The
  block-table width bounds the grid's S dimension.
* ``flash_decode_paged_q8``: the hybrid-precision tier (the YOCO
  ReRAM–SRAM split applied to the KV cache) — cold pages stream from an
  int8 pool with per-page, per-head absmax scales, the last ``hot_window``
  pages read from the fp pool where all writes land.
* ``flash_decode_paged_mla``: absorbed multi-head-latent-attention over a
  paged LATENT pool ``(num_pages, page_size, r + d_rope)`` — one pool, no
  separate K/V. Each fetched latent tile is used twice: full width as the
  keys (against the absorbed+rope query), first ``r`` columns as the
  values. ``W_uv`` is applied once, outside the loop, by the caller.
* ``flash_decode_paged_mla_q8``: the latent pool's hybrid tier — cold
  ``cl`` pages stream as int8 with ONE per-page absmax scale (the latent
  is quantized *before* the W_uk/W_uv expansion; see
  ``runtime/kv_quant.py`` for the error-model discussion), hot pages from
  the fp latent pool. Same hotness rule, same tier routing in the index
  maps, same fetch-once/use-twice split as the fp MLA kernel.

Grid: (B, S/bs) with S innermost ("arbitrary"); each step fetches one
whole key tile across every KV head — a ``(1, bs, Hkv, dh)`` block, whose
last two dims equal the pool's, which is what Mosaic's (8, 128) tiling
rule asks of a block — and reduces all heads at once in head-batched dots
against the resident ``(Hkv, G, dh)`` query block (GQA group G = H // Hkv).
B is parallel. The MLA kernels degenerate the head axis to 1 (the latent
cache is shared by every head) and keep all H queries resident in the
one cell. Per-page int8 scales ride as ``(P, Hkv, 1)`` / ``(P, 1, 1)``
VMEM blocks for the same tiling reason.

Every entrypoint also takes an optional ``layer`` operand: the tensors are
then the layer-stacked buffers the model's layer scan carries, and the
grid reads layer ``layer`` of them in place (:func:`_flash_core`).

CPU CI runs these same kernel bodies with ``interpret=True``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BS = 512          # key-tile length along the cache S axis
TILE_ELEMS = 1 << 18      # K/V tile elements (all heads) per contiguous step
NEG_INF = float('-inf')


# ----------------------------------------------------------------------------
# the one online-softmax compute body
# ----------------------------------------------------------------------------
def _live_block_range(pos, win, bs: int):
    """[first, last] inclusive range of key blocks with any valid key for a
    request at ``pos`` with sliding window ``win``. The index maps and the
    kernel's compute guard must agree on this range: a tile is fetched iff
    it is computed."""
    first = jnp.maximum(pos - win + 1, 0) // bs
    last = jnp.maximum(pos, 0) // bs
    return first, last


def _head_major(x):
    """(bs, Hkv, d) fetched page tile -> (Hkv, bs, d): the batch-leading
    layout the head-batched dots take."""
    return jnp.swapaxes(x, 0, 1)


def _softmax_tile(pos, win, s, q_ref, load_kv, o_ref,
                  acc_ref, m_ref, l_ref, *, bs: int, s_steps: int,
                  scale: float, chunk: int = 1, group: int = 1, off=None):
    """One online-softmax step over key tile ``s`` — THE compute path every
    flash-decode entrypoint reduces through; only the scalar plumbing and
    the K/V tile loader differ per layout. ``load_kv() -> (k, v)`` f32
    head-major ``(Hg, bs, d)`` tiles (Hg = the KV heads of the block, 1 for
    the MLA latent); it runs under the live-tile predicate so dead steps
    skip both the load and the compute. Every head of the block reduces in
    one head-batched pair of dots against its ``(Hg, rows, dk)`` queries.

    ``chunk > 1`` is the chunked-prefill shape: the resident query rows
    cover ``chunk`` consecutive positions (``group`` query heads each,
    row i sits at absolute position ``off + i // group``), so the causal
    mask goes per-row. The caller passes the *fetch-union* scalars —
    ``pos`` = the chunk's last (clamped) position, ``win`` = the per-row
    window + (chunk - 1) — so the live-block range covers every row;
    within a tile each row re-derives its own validity from ``off``.
    ``chunk == 1`` (decoding) keeps the historical single-position mask
    bit-for-bit."""
    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    first, last = _live_block_range(pos, win, bs)
    live = (s >= first) & (s <= last)

    @pl.when(live)
    def _tile():
        q = q_ref[0].astype(jnp.float32)                     # (Hg, rows, dk)
        k, v = load_kv()                                     # (Hg, bs, d)
        kpos = s * bs + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bs), 2)
        if chunk == 1:
            valid = (kpos <= pos) & (kpos > pos - win)
        else:
            rows = q_ref.shape[2]
            qp = off + jax.lax.broadcasted_iota(
                jnp.int32, (1, rows, 1), 1) // group         # (1, rows, 1)
            row_win = win - (chunk - 1)
            valid = (kpos <= qp) & (kpos > qp - row_win)     # (1, rows, bs)
        logits = jax.lax.dot_general(
            q, k, dimension_numbers=(((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale      # (Hg, rows, bs)
        logits = jnp.where(valid, logits, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        # all-masked guards: exp(-inf - -inf) must contribute 0, not 1
        alpha = jnp.where(jnp.isfinite(m_prev),
                          jnp.exp(m_prev - m_new), 0.0)
        p = jnp.where(valid, jnp.exp(logits - m_new), 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(s == s_steps - 1)
    def _epilogue():
        # the one output conversion: normalize once, after the full reduction
        o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


# ----------------------------------------------------------------------------
# the one harness: scalar-prefetch grid, layout-parameterized (maps, loader)
# ----------------------------------------------------------------------------
def _core_kernel(*refs, ns: int, nt: int, loader, bs: int, s_steps: int,
                 scale: float, chunk: int = 1, group: int = 1,
                 off_idx=None):
    """The single kernel body behind every entrypoint. Argument layout (the
    PrefetchScalarGridSpec convention): ``ns`` scalar-prefetch refs
    (pos, window, then layout extras such as block tables / hot window),
    the query ref, ``nt`` layout tensor refs, the output ref, and the three
    online-softmax scratch refs. ``chunk``/``group``/``off_idx`` are the
    chunked-prefill parameters (``off_idx`` names the scalar operand that
    carries each request's chunk start position)."""
    scalars = refs[:ns]
    q_ref = refs[ns]
    t_refs = refs[ns + 1:ns + 1 + nt]
    o_ref, acc_ref, m_ref, l_ref = refs[ns + 1 + nt:]
    b = pl.program_id(0)
    s = pl.program_id(1)
    pos, win = scalars[0][b], scalars[1][b]
    off = scalars[off_idx][b] if off_idx is not None else None
    load_kv = loader(scalars, t_refs, b, s, pos, win)
    _softmax_tile(pos, win, s, q_ref, load_kv, o_ref, acc_ref, m_ref, l_ref,
                  bs=bs, s_steps=s_steps, scale=scale, chunk=chunk,
                  group=group, off=off)


def _flash_core(q: jnp.ndarray, scalars, tensors, tensor_specs, *, loader,
                out_width: int, bs: int, s_steps: int, scale: float,
                interpret: bool, chunk: int = 1, group: int = 1,
                off_idx=None, layer=None) -> jnp.ndarray:
    """Run the flash-decode grid over ``q`` (B, Hg, rows, dk) with a
    layout-supplied ``(index_maps, loader)`` pair: ``tensor_specs`` carry
    the layout's data-dependent index maps (one BlockSpec per tensor
    operand, each fetching a whole page/tile across every head, so the
    last two block dims always equal the array's — the Mosaic tiling
    rule), ``loader`` turns the fetched refs into f32 head-major K/V
    tiles. ``scalars`` ride in scalar-prefetch operands (pos and window
    first — the core reads those itself). Returns (B, Hg, rows, out_width)
    f32.

    ``layer`` (a traced int32 scalar, or None): every tensor operand one
    rank above its block is a layer-stacked buffer ``(L, ...)`` and the
    grid reads layer ``layer`` of it in place. The index rides as the last
    scalar-prefetch operand; each such tensor's BlockSpec gains a squeezed
    leading block dim whose index is that layer, so the layout's maps and
    loader see exactly the single-layer blocks and refs they would without
    it, and the kernel DMAs the live pages straight out of the stacked
    pool (no per-layer copy of the pool is ever made). A tensor at its
    block's rank is one layer's already (the q8 wrappers slice the small
    per-page scales at ``layer`` before the call) and is read as it is."""
    b, hg, rows, dk = q.shape
    grid = (b, s_steps)
    if layer is not None:
        scalars = tuple(scalars) + (
            jnp.asarray(layer, jnp.int32).reshape(1),)
        tensor_specs = [_layer_spec(spec, t.ndim > len(spec.block_shape))
                        for t, spec in zip(tensors, tensor_specs)]

    def qo_map(bb, s, *sr):
        del s, sr
        return (bb, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=grid,
        in_specs=[pl.BlockSpec((1, hg, rows, dk), qo_map)]
        + list(tensor_specs),
        out_specs=pl.BlockSpec((1, hg, rows, out_width), qo_map),
        scratch_shapes=[
            pltpu.VMEM((hg, rows, out_width), jnp.float32),  # unnormalized
            pltpu.VMEM((hg, rows, 1), jnp.float32),          # running max
            pltpu.VMEM((hg, rows, 1), jnp.float32),          # running sum
        ],
    )
    return pl.pallas_call(
        functools.partial(_core_kernel, ns=len(scalars), nt=len(tensors),
                          loader=loader, bs=bs, s_steps=s_steps, scale=scale,
                          chunk=chunk, group=group, off_idx=off_idx),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hg, rows, out_width),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary'),
        ),
        interpret=interpret,
    )(*scalars, q, *tensors)


def _layer_spec(spec: pl.BlockSpec, stacked: bool) -> pl.BlockSpec:
    """A single-layer tensor BlockSpec under a layer operand (the last
    scalar-prefetch ref, hidden from the layout's own map). ``stacked``:
    lifted onto the layer-stacked buffer, a squeezed leading block dim
    indexed by the layer."""
    inner = spec.index_map
    if not stacked:
        return pl.BlockSpec(spec.block_shape,
                            lambda bb, s, *sr: inner(bb, s, *sr[:-1]))

    def index_map(bb, s, *sr):
        return (sr[-1][0],) + tuple(inner(bb, s, *sr[:-1]))
    return pl.BlockSpec((None,) + tuple(spec.block_shape), index_map)


def _clamped_block(s, pos_ref, win_ref, b, bs: int):
    """Block index actually fetched at grid step ``s``: dead steps revisit
    the nearest live block so their DMA is elided by the pipeline."""
    first, last = _live_block_range(pos_ref[b], win_ref[b], bs)
    return jnp.clip(s, first, last)


def _fp_loader(scalars, t_refs, bb, s, pos, win):
    """Plain fp K/V loader (contiguous and paged GQA layouts): the fetched
    (1, bs, Hkv, dh) blocks, read into f32 head-major tiles."""
    del scalars, bb, s, pos, win
    k_ref, v_ref = t_refs
    return lambda: (_head_major(k_ref[0].astype(jnp.float32)),
                    _head_major(v_ref[0].astype(jnp.float32)))


# ----------------------------------------------------------------------------
# contiguous layouts: streamed (legacy baseline) and prefetch
# ----------------------------------------------------------------------------
@functools.partial(jax.jit,
                   static_argnames=('scale', 'bs', 'prefetch', 'interpret'))
def flash_decode_gqa(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     pos: jnp.ndarray, window: jnp.ndarray,
                     layer: jnp.ndarray = None, *,
                     scale: float, bs: int = DEFAULT_BS,
                     prefetch: bool = True,
                     interpret: bool = False) -> jnp.ndarray:
    """Single-token GQA decode attention over a length-masked contiguous
    KV cache.

    q:      (B, Hkv, G, dh) — query heads grouped by their KV head
    k, v:   (B, S, Hkv, dh) — cache; S % bs == 0 (pad in the wrapper)
    pos:    (B,) int32      — per-request absolute position; keys at
                              kpos <= pos[b] are attended
    window: (B,) int32      — per-request sliding window (>= S+1 disables)
    layer:  None, or int32 scalar — k/v are layer-stacked (L, B, S, Hkv,
            dh) caches and the kernel reads layer ``layer`` in place
    prefetch: data-dependent index maps (dead tiles never fetched). False
              is the legacy streamed baseline: identity maps, every tile
              DMA'd, masked tiles skip compute only. Same harness, same
              body — the outputs are bitwise equal.

    Returns (B, Hkv, G, dh) f32.
    """
    b, hkv, g, dh = q.shape
    s_max = k.shape[-3]
    assert k.shape[-4:] == (b, s_max, hkv, dh) and v.shape == k.shape, \
        (q.shape, k.shape, v.shape)
    assert s_max % bs == 0, (s_max, bs)
    assert pos.shape == (b,) and window.shape == (b,)
    s_steps = s_max // bs

    if prefetch:
        def kv_map(bb, s, pos_ref, win_ref):
            return (bb, _clamped_block(s, pos_ref, win_ref, bb, bs), 0, 0)
    else:
        def kv_map(bb, s, pos_ref, win_ref):
            del pos_ref, win_ref
            return (bb, s, 0, 0)

    return _flash_core(
        q,
        scalars=(pos.astype(jnp.int32), window.astype(jnp.int32)),
        tensors=(k, v),
        tensor_specs=[pl.BlockSpec((1, bs, hkv, dh), kv_map),
                      pl.BlockSpec((1, bs, hkv, dh), kv_map)],
        loader=_fp_loader,
        out_width=dh, bs=bs, s_steps=s_steps, scale=scale,
        interpret=interpret, layer=layer)


# ----------------------------------------------------------------------------
# paged GQA layout
# ----------------------------------------------------------------------------
@functools.partial(jax.jit,
                   static_argnames=('scale', 'chunk', 'interpret'))
def flash_decode_gqa_paged(q: jnp.ndarray, k_pages: jnp.ndarray,
                           v_pages: jnp.ndarray, pos: jnp.ndarray,
                           window: jnp.ndarray, block_tables: jnp.ndarray,
                           offset: jnp.ndarray = None,
                           layer: jnp.ndarray = None, *, scale: float,
                           chunk: int = 1,
                           interpret: bool = False) -> jnp.ndarray:
    """Single-token GQA decode attention over a *paged* KV pool.

    q:            (B, Hkv, G, dh)
    k/v_pages:    (P, page_size, Hkv, dh) — pool shared by all requests;
                  each grid step fetches one whole page, every head
    pos:          (B,) int32 per-request absolute position
    window:       (B,) int32 per-request sliding window
    block_tables: (B, W) int32 — logical key block i of request b lives in
                  physical page block_tables[b, i]; W bounds the grid's S
                  dimension (size it to ceil(max_live / page_size))
    layer:        None, or int32 scalar — the pools are layer-stacked
                  (L, P, page_size, Hkv, dh) and the grid DMAs layer
                  ``layer``'s pages straight out of the stack

    ``chunk > 1`` is the chunked-prefill shape: the G axis widens to
    chunk * G (row i = query head i % G at position offset[b] + i // G),
    ``offset`` (B,) int32 carries each chunk's start, and the caller must
    pass fetch-union scalars — ``pos`` = the chunk's LAST valid position
    (clamped below the prompt length so block-table indexing stays in
    range) and ``window`` = per-row window + (chunk - 1). Use the
    :func:`flash_chunk_paged` wrapper, which derives all three.

    Returns (B, Hkv, G, dh) f32 (chunked: (B, Hkv, chunk * G, dh)).
    """
    b, hkv, g, dh = q.shape
    page_size, hkv_k, dh_k = k_pages.shape[-3:]
    assert (hkv_k, dh_k) == (hkv, dh), (q.shape, k_pages.shape)
    assert v_pages.shape == k_pages.shape
    assert pos.shape == (b,) and window.shape == (b,)
    assert block_tables.ndim == 2 and block_tables.shape[0] == b
    assert g % chunk == 0, (g, chunk)
    assert (offset is None) == (chunk == 1), (offset, chunk)
    s_steps = block_tables.shape[1]

    def kv_map(bb, s, pos_ref, win_ref, bt_ref, *rest):
        blk = _clamped_block(s, pos_ref, win_ref, bb, page_size)
        return (bt_ref[bb, blk], 0, 0, 0)

    scalars = (pos.astype(jnp.int32), window.astype(jnp.int32),
               block_tables.astype(jnp.int32))
    if chunk > 1:
        scalars = scalars + (offset.astype(jnp.int32),)
    kv_block = (1, page_size, hkv, dh)
    return _flash_core(
        q,
        scalars=scalars,
        tensors=(k_pages, v_pages),
        tensor_specs=[pl.BlockSpec(kv_block, kv_map),
                      pl.BlockSpec(kv_block, kv_map)],
        loader=_fp_loader,
        out_width=dh, bs=page_size, s_steps=s_steps, scale=scale,
        interpret=interpret, chunk=chunk, group=g // chunk,
        off_idx=3 if chunk > 1 else None, layer=layer)


# ----------------------------------------------------------------------------
# paged MLA latent layout
# ----------------------------------------------------------------------------
@functools.partial(jax.jit,
                   static_argnames=('scale', 'r', 'chunk', 'interpret'))
def flash_decode_mla_paged(q: jnp.ndarray, c_pages: jnp.ndarray,
                           pos: jnp.ndarray, window: jnp.ndarray,
                           block_tables: jnp.ndarray,
                           offset: jnp.ndarray = None,
                           layer: jnp.ndarray = None, *, scale: float,
                           r: int, chunk: int = 1,
                           interpret: bool = False) -> jnp.ndarray:
    """Single-token absorbed-MLA decode attention over a *paged* latent pool.

    q:            (B, 1, H, r + d_rope) — the ABSORBED query: per head,
                  ``q_nope @ W_uk`` (width r) concatenated with the rope
                  query (width d_rope); on the concatenated layout the
                  absorbed score ``q_abs · ckv^T + q_rope · krope^T`` is a
                  single dot product against the latent tile
    c_pages:      (P, page_size, r + d_rope) — latent pool shared by all
                  requests: ``ckv`` in the first r columns, ``krope`` in
                  the last d_rope (one pool — MLA has no separate K/V)
    pos:          (B,) int32 per-request absolute position
    window:       (B,) int32 per-request sliding window (>= S+1 disables;
                  MLA archs here never window — the operand exists so the
                  kernel shares the core with the GQA family verbatim)
    block_tables: (B, W) int32 — same contract as
                  :func:`flash_decode_gqa_paged`; dead steps clamp onto the
                  nearest live block so their DMA is elided
    r:            static latent rank — the value width (``W_uv`` is applied
                  once OUTSIDE the kernel, on the normalized output)
    layer:        None, or int32 scalar — ``c_pages`` is the layer-stacked
                  (L, P, page_size, r + d_rope) pool, read in place

    ``chunk > 1`` widens the resident H axis to chunk * H (row i = head
    i % H at position offset[b] + i // H) with the same fetch-union
    scalar contract as :func:`flash_decode_gqa_paged`; use the
    :func:`flash_chunk_paged_mla` wrapper.

    Returns (B, 1, H, r) f32: the latent-space attention output
    (chunked: (B, 1, chunk * H, r)).
    """
    b, one, h, dk = q.shape
    assert one == 1, q.shape
    page_size, dk_c = c_pages.shape[-2:]
    assert dk_c == dk, (q.shape, c_pages.shape)
    assert 0 < r < dk, (r, dk)
    assert pos.shape == (b,) and window.shape == (b,)
    assert block_tables.ndim == 2 and block_tables.shape[0] == b
    assert h % chunk == 0, (h, chunk)
    assert (offset is None) == (chunk == 1), (offset, chunk)
    s_steps = block_tables.shape[1]

    def c_map(bb, s, pos_ref, win_ref, bt_ref, *rest):
        blk = _clamped_block(s, pos_ref, win_ref, bb, page_size)
        return (bt_ref[bb, blk], 0, 0)

    def mla_loader(scalars, t_refs, bb, s, pos_, win_):
        c_ref, = t_refs

        def load():
            # fetch once, use twice: full width = keys, first r cols = values
            lat = c_ref[...].astype(jnp.float32)       # (1, bs, r + d_rope)
            return lat, lat[:, :, :r]
        return load

    scalars = (pos.astype(jnp.int32), window.astype(jnp.int32),
               block_tables.astype(jnp.int32))
    if chunk > 1:
        scalars = scalars + (offset.astype(jnp.int32),)
    return _flash_core(
        q,
        scalars=scalars,
        tensors=(c_pages,),
        tensor_specs=[pl.BlockSpec((1, page_size, dk), c_map)],
        loader=mla_loader,
        out_width=r, bs=page_size, s_steps=s_steps, scale=scale,
        interpret=interpret, chunk=chunk, group=h // chunk,
        off_idx=3 if chunk > 1 else None, layer=layer)


# ----------------------------------------------------------------------------
# hybrid-precision tiers: hot/cold routing shared by the q8 layouts
# ----------------------------------------------------------------------------
def _blk_hot(bb, s, pos_ref, win_ref, hw_ref, bs: int):
    """(clamped block, is-hot) for grid step ``s`` — the ONE hotness rule
    (shared with ``runtime.kv_quant``): block ``s`` of a request at ``pos``
    is hot iff ``s > pos // page_size - hw``."""
    first, last = _live_block_range(pos_ref[bb], win_ref[bb], bs)
    blk = jnp.clip(s, first, last)
    return blk, blk > last - hw_ref[0]


def _tier_maps(page_size: int, pool_ndim: int):
    """(fp_map, q8_map, scale_map) index maps for a paged hot/cold tier
    pair of rank-``pool_ndim`` pools: a hot step fetches the fp page and
    parks the int8 fetch on the garbage page (repeated index, DMA elided);
    a cold step does the reverse. ``scale_map`` follows the cold tier over
    the rank-3 per-page scale operand (``(P, Hkv, 1)`` for GQA,
    ``(P, 1, 1)`` for MLA), one whole page's scales per step."""
    zeros = (0,) * (pool_ndim - 1)

    def fp_map(bb, s, pos_ref, win_ref, bt_ref, hw_ref):
        blk, hot = _blk_hot(bb, s, pos_ref, win_ref, hw_ref, page_size)
        return (jnp.where(hot, bt_ref[bb, blk], 0),) + zeros

    def q8_map(bb, s, pos_ref, win_ref, bt_ref, hw_ref):
        blk, hot = _blk_hot(bb, s, pos_ref, win_ref, hw_ref, page_size)
        return (jnp.where(hot, 0, bt_ref[bb, blk]),) + zeros

    def scale_map(bb, s, pos_ref, win_ref, bt_ref, hw_ref):
        blk, hot = _blk_hot(bb, s, pos_ref, win_ref, hw_ref, page_size)
        return (jnp.where(hot, 0, bt_ref[bb, blk]), 0, 0)

    return fp_map, q8_map, scale_map


def _hot_now(scalars, s, pos, win, page_size: int):
    """The loader's side of the hotness rule (same clamp as the maps)."""
    first, last = _live_block_range(pos, win, page_size)
    return jnp.clip(s, first, last) > last - scalars[3][0]


def _dequant(q_tile, scale, like_ref):
    """The one dequantization per fetched int8 tile, rounded through the
    serving dtype so the tier mix is bit-identical with the einsum
    oracle's ``dequant_gather``."""
    return (q_tile.astype(jnp.float32) * scale) \
        .astype(like_ref.dtype).astype(jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=('scale', 'interpret'))
def flash_decode_gqa_paged_q8(q: jnp.ndarray, k_pages: jnp.ndarray,
                              v_pages: jnp.ndarray, kq_pages: jnp.ndarray,
                              vq_pages: jnp.ndarray, k_scales: jnp.ndarray,
                              v_scales: jnp.ndarray, pos: jnp.ndarray,
                              window: jnp.ndarray,
                              block_tables: jnp.ndarray,
                              hot_window: jnp.ndarray,
                              layer: jnp.ndarray = None, *, scale: float,
                              interpret: bool = False) -> jnp.ndarray:
    """:func:`flash_decode_gqa_paged` over a hybrid-precision pool pair.

    k/v_pages:    (P, page_size, Hkv, dh) full-precision pool — the "SRAM"
                  tier; holds the last ``hot_window`` pages of each request
                  (all writes land here)
    kq/vq_pages:  (P, page_size, Hkv, dh) int8 — the "ReRAM" tier; valid
                  for pages older than the hot window (the scheduler
                  quantizes pages as they age out)
    k/v_scales:   (P, Hkv) f32 per-page, per-head absmax scales
    hot_window:   (1,) int32, in pages, >= 1 (the page being written is
                  always hot). >= W reads everything from the fp pool.
    layer:        None, or int32 scalar — pools and scales are
                  layer-stacked (a leading (L,) dim); the pools are read
                  in place, the scales sliced at ``layer`` first (the
                  kernel's (P, Hkv, 1) view of them is a relayout, which
                  over the whole stack would copy every layer's scales
                  on every call)

    Block ``s`` of a request at ``pos`` is hot iff
    ``s > pos // page_size - hot_window``; a hot grid step fetches the fp
    page and clamps the int8 fetch onto the garbage page (and vice versa),
    so each tile pays one tier's HBM bytes, never both. The scales ride
    as (P, Hkv, 1) VMEM blocks — one page's per-head column per step,
    broadcast along the fetched tile's head axis.
    """
    b, hkv, g, dh = q.shape
    page_size, hkv_k, dh_k = k_pages.shape[-3:]
    assert (hkv_k, dh_k) == (hkv, dh), (q.shape, k_pages.shape)
    assert v_pages.shape == k_pages.shape
    assert kq_pages.shape == k_pages.shape and kq_pages.dtype == jnp.int8
    assert vq_pages.shape == k_pages.shape and vq_pages.dtype == jnp.int8
    assert k_scales.shape == v_scales.shape == k_pages.shape[:-3] + (hkv,)
    assert pos.shape == (b,) and window.shape == (b,)
    assert block_tables.ndim == 2 and block_tables.shape[0] == b
    assert hot_window.shape == (1,)
    s_steps = block_tables.shape[1]
    fp_map, q8_map, scale_map = _tier_maps(page_size, 4)
    if layer is not None:
        k_scales, v_scales = k_scales[layer], v_scales[layer]

    def q8_loader(scalars, t_refs, bb, s, pos_, win_):
        k_ref, v_ref, kq_ref, vq_ref, ks_ref, vs_ref = t_refs
        hot = _hot_now(scalars, s, pos_, win_, page_size)

        def load():
            k = jnp.where(hot, k_ref[0].astype(jnp.float32),
                          _dequant(kq_ref[0], ks_ref[...], k_ref))
            v = jnp.where(hot, v_ref[0].astype(jnp.float32),
                          _dequant(vq_ref[0], vs_ref[...], v_ref))
            return _head_major(k), _head_major(v)
        return load

    kv_block = (1, page_size, hkv, dh)
    sc_block = (1, hkv, 1)
    return _flash_core(
        q,
        scalars=(pos.astype(jnp.int32), window.astype(jnp.int32),
                 block_tables.astype(jnp.int32),
                 hot_window.astype(jnp.int32)),
        tensors=(k_pages, v_pages, kq_pages, vq_pages,
                 k_scales.astype(jnp.float32)[..., None],
                 v_scales.astype(jnp.float32)[..., None]),
        tensor_specs=[
            pl.BlockSpec(kv_block, fp_map),
            pl.BlockSpec(kv_block, fp_map),
            pl.BlockSpec(kv_block, q8_map),
            pl.BlockSpec(kv_block, q8_map),
            pl.BlockSpec(sc_block, scale_map),
            pl.BlockSpec(sc_block, scale_map),
        ],
        loader=q8_loader,
        out_width=dh, bs=page_size, s_steps=s_steps, scale=scale,
        interpret=interpret, layer=layer)


@functools.partial(jax.jit,
                   static_argnames=('scale', 'r', 'interpret'))
def flash_decode_mla_paged_q8(q: jnp.ndarray, c_pages: jnp.ndarray,
                              cq_pages: jnp.ndarray, c_scales: jnp.ndarray,
                              pos: jnp.ndarray, window: jnp.ndarray,
                              block_tables: jnp.ndarray,
                              hot_window: jnp.ndarray,
                              layer: jnp.ndarray = None, *, scale: float,
                              r: int, interpret: bool = False) -> jnp.ndarray:
    """:func:`flash_decode_mla_paged` over a hybrid-precision latent pool.

    c_pages:   (P, page_size, r + d_rope) fp latent pool — the hot tier;
               all writes (prefill + decode) land here
    cq_pages:  (P, page_size, r + d_rope) int8 — the cold tier: aged-out
               latent pages quantized with ONE per-page absmax scale,
               *before* the W_uk/W_uv expansion
    c_scales:  (P, 1) f32 per-page absmax scales
    hot_window: (1,) int32, in pages, >= 1; >= W never reads the int8 tier
               (bit-exact with :func:`flash_decode_mla_paged`)
    layer:     None, or int32 scalar — pools and scales are layer-stacked
               (a leading (L,) dim); pools read in place, scales sliced at
               ``layer`` first (as :func:`flash_decode_gqa_paged_q8`)

    Same hotness rule and tier routing as :func:`flash_decode_gqa_paged_q8`
    (one tier's DMA per tile, dequant in VMEM rounded through the serving
    dtype), same fetch-once/use-twice latent split as the fp MLA kernel.

    Returns (B, 1, H, r) f32: the latent-space attention output.
    """
    b, one, h, dk = q.shape
    assert one == 1, q.shape
    page_size, dk_c = c_pages.shape[-2:]
    assert dk_c == dk, (q.shape, c_pages.shape)
    assert 0 < r < dk, (r, dk)
    assert cq_pages.shape == c_pages.shape and cq_pages.dtype == jnp.int8
    assert c_scales.shape == c_pages.shape[:-2] + (1,), c_scales.shape
    assert pos.shape == (b,) and window.shape == (b,)
    assert block_tables.ndim == 2 and block_tables.shape[0] == b
    assert hot_window.shape == (1,)
    s_steps = block_tables.shape[1]
    c_fp_map, c_q8_map, cs_map = _tier_maps(page_size, 3)
    if layer is not None:
        c_scales = c_scales[layer]

    def mla_q8_loader(scalars, t_refs, bb, s, pos_, win_):
        c_ref, cq_ref, cs_ref = t_refs
        hot = _hot_now(scalars, s, pos_, win_, page_size)

        def load():
            lat = jnp.where(hot, c_ref[...].astype(jnp.float32),
                            _dequant(cq_ref[...], cs_ref[...], c_ref))
            return lat, lat[:, :, :r]           # (1, bs, r + d_rope)
        return load

    return _flash_core(
        q,
        scalars=(pos.astype(jnp.int32), window.astype(jnp.int32),
                 block_tables.astype(jnp.int32),
                 hot_window.astype(jnp.int32)),
        tensors=(c_pages, cq_pages,
                 c_scales.astype(jnp.float32)[..., None]),
        tensor_specs=[
            pl.BlockSpec((1, page_size, dk), c_fp_map),
            pl.BlockSpec((1, page_size, dk), c_q8_map),
            pl.BlockSpec((1, 1, 1), cs_map),
        ],
        loader=mla_q8_loader,
        out_width=r, bs=page_size, s_steps=s_steps, scale=scale,
        interpret=interpret, layer=layer)


# ----------------------------------------------------------------------------
# shape-flexible wrappers (the five public entrypoints)
# ----------------------------------------------------------------------------
def _pick_bs(s_max: int, bs: int) -> int:
    """Key-tile length: the largest tile <= ``bs`` (halving down to 128)
    whose padding stays under max(128, s_max/8).

    The old rule rounded ``s_max`` UP to the next power of two before
    clamping, so a non-power-of-two cache could nearly double: S=520 picked
    bs=512 and padded to 1024 (+504 dead positions). The cap bounds that
    blowup at ~12.5% while still preferring big tiles (fewer grid steps);
    chasing the absolute minimum pad instead would collapse barely-
    unaligned caches to 128-wide tiles and 4x the grid — a bad trade, since
    pad tiles are causally dead and the prefetch path never fetches them."""
    if bs <= 128:
        return bs                   # caller-tightened VMEM cap wins
    limit = max(128, s_max // 8)
    tile = bs
    while tile > 128:
        if -(-s_max // tile) * tile - s_max <= limit:
            return tile
        tile //= 2
    return 128                      # pad < 128 <= limit always holds here


def _vmem_bs_cap(hkv: int, dh: int) -> int:
    """Largest power-of-two key-tile length whose (bs, Hkv, dh) K/V tile
    stays within ``TILE_ELEMS``: every head of a tile is fetched at once,
    so wide-head caches take shorter tiles to fit the scoped VMEM."""
    cap = 8
    while cap * 2 * hkv * dh <= TILE_ELEMS:
        cap *= 2
    return cap


def _norm_scalar_vec(x, b: int, fill=None) -> jnp.ndarray:
    """None | int | traced scalar | (B,)/(B,1) array -> (B,) int32."""
    if x is None:
        return jnp.full((b,), fill, jnp.int32)
    x = jnp.asarray(x, jnp.int32)
    return jnp.broadcast_to(x.reshape(-1) if x.ndim else x, (b,))


def _interpret_default(interpret):
    if interpret is None:
        from repro.kernels import ops
        return ops._interpret()
    return interpret


def flash_decode(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                 pos: jnp.ndarray, *, scale: float,
                 window=None, bs: int = DEFAULT_BS,
                 interpret=None, impl: str = 'prefetch',
                 layer=None) -> jnp.ndarray:
    """Shape-flexible wrapper around the contiguous flash-decode kernel.

    q:   (B, 1, H, dh) or (B, H, dh) — the single decode-step query
    k,v: (B, S_max, Hkv, dh) KV cache, any dtype (bf16 serving layout)
    pos: scalar or (B,) int — per-request absolute positions
    window: None | int | traced scalar | (B,) — sliding-window width
    impl: 'prefetch' (scalar-prefetch block skipping, default) or
          'streamed' (legacy: every tile DMA'd; kept as the benchmark
          baseline for the dead-tile bandwidth comparison)
    layer: None, or int32 scalar — k/v are layer-stacked (L, B, S_max,
           Hkv, dh) caches, read at ``layer`` in place (a cache whose
           S_max needs tile padding pads that one layer's slice instead)

    Returns attention output shaped like q, in v.dtype (the one conversion
    back to the serving dtype happens here, after the fused normalize).
    """
    assert impl in ('prefetch', 'streamed'), impl
    squeeze = q.ndim == 4
    if squeeze:
        assert q.shape[1] == 1, q.shape
        q = q[:, 0]
    b, h, dh = q.shape
    s_max, hkv = k.shape[-3], k.shape[-2]
    g = h // hkv
    qg = q.reshape(b, hkv, g, dh)      # same (hkv, g) grouping as _sdpa
    pos = _norm_scalar_vec(pos, b)
    win = _norm_scalar_vec(window, b, fill=s_max + 1)
    bs_eff = _pick_bs(s_max, min(bs, _vmem_bs_cap(hkv, dh)))
    pad = (-s_max) % bs_eff
    if pad:
        if layer is not None:
            k, v, layer = k[layer], v[layer], None
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    out = flash_decode_gqa(qg, k, v, pos, win, layer, scale=scale,
                           bs=bs_eff, prefetch=(impl == 'prefetch'),
                           interpret=_interpret_default(interpret))
    out = out.reshape(b, h, dh).astype(v.dtype)
    return out[:, None] if squeeze else out


def flash_decode_paged(q: jnp.ndarray, k_pages: jnp.ndarray,
                       v_pages: jnp.ndarray, pos: jnp.ndarray,
                       block_tables: jnp.ndarray, *, scale: float,
                       window=None, interpret=None,
                       layer=None) -> jnp.ndarray:
    """Shape-flexible wrapper around :func:`flash_decode_gqa_paged`.

    q: (B, 1, H, dh) or (B, H, dh); k/v_pages: (P, page_size, Hkv, dh), or
    layer-stacked (L, P, page_size, Hkv, dh) read at ``layer``; pos:
    scalar or (B,); block_tables: (B, W) int32.

    Returns attention output shaped like q, in v_pages.dtype.
    """
    squeeze = q.ndim == 4
    if squeeze:
        assert q.shape[1] == 1, q.shape
        q = q[:, 0]
    b, h, dh = q.shape
    hkv = k_pages.shape[-2]
    g = h // hkv
    qg = q.reshape(b, hkv, g, dh)
    s_logical = block_tables.shape[1] * k_pages.shape[-3]
    pos = _norm_scalar_vec(pos, b)
    win = _norm_scalar_vec(window, b, fill=s_logical + 1)
    out = flash_decode_gqa_paged(qg, k_pages, v_pages, pos, win,
                                 block_tables, None, layer, scale=scale,
                                 interpret=_interpret_default(interpret))
    out = out.reshape(b, h, dh).astype(v_pages.dtype)
    return out[:, None] if squeeze else out


def flash_decode_paged_mla(q: jnp.ndarray, c_pages: jnp.ndarray,
                           pos: jnp.ndarray, block_tables: jnp.ndarray, *,
                           r: int, scale: float, window=None,
                           interpret=None, layer=None) -> jnp.ndarray:
    """Shape-flexible wrapper around :func:`flash_decode_mla_paged`.

    q: (B, 1, H, r + d_rope) or (B, H, r + d_rope) — the absorbed+rope
    query; c_pages: (P, page_size, r + d_rope) latent pool, or the
    layer-stacked (L, P, ...) pool read at ``layer``; pos: scalar or
    (B,); block_tables: (B, W) int32; ``r``: static latent rank.

    Returns the latent-space attention output shaped like q with last dim
    ``r``, in f32 (the caller applies ``W_uv`` once and converts — the MLA
    analogue of the single output conversion).
    """
    had_q_axis = q.ndim == 4
    if had_q_axis:
        assert q.shape[1] == 1, q.shape
    else:
        q = q[:, None]               # (B, H, dk) -> (B, 1, H, dk)
    b = q.shape[0]
    s_logical = block_tables.shape[1] * c_pages.shape[-2]
    pos = _norm_scalar_vec(pos, b)
    win = _norm_scalar_vec(window, b, fill=s_logical + 1)
    out = flash_decode_mla_paged(q, c_pages, pos, win, block_tables,
                                 None, layer, scale=scale, r=r,
                                 interpret=_interpret_default(interpret))
    return out if had_q_axis else out[:, 0]


def flash_decode_paged_q8(q: jnp.ndarray, k_pages: jnp.ndarray,
                          v_pages: jnp.ndarray, kq_pages: jnp.ndarray,
                          vq_pages: jnp.ndarray, k_scales: jnp.ndarray,
                          v_scales: jnp.ndarray, pos: jnp.ndarray,
                          block_tables: jnp.ndarray,
                          hot_window: jnp.ndarray, *, scale: float,
                          window=None, interpret=None,
                          layer=None) -> jnp.ndarray:
    """Shape-flexible wrapper around :func:`flash_decode_gqa_paged_q8`.

    q: (B, 1, H, dh) or (B, H, dh); pools: (P, page_size, Hkv, dh) fp +
    int8 pair; scales: (P, Hkv); pos: scalar or (B,); block_tables:
    (B, W) int32; hot_window: int or (1,) int32. With ``layer``, pools and
    scales are layer-stacked (a leading (L,) dim), read at ``layer``.

    Returns attention output shaped like q, in v_pages.dtype.
    """
    squeeze = q.ndim == 4
    if squeeze:
        assert q.shape[1] == 1, q.shape
        q = q[:, 0]
    b, h, dh = q.shape
    hkv = k_pages.shape[-2]
    g = h // hkv
    qg = q.reshape(b, hkv, g, dh)
    s_logical = block_tables.shape[1] * k_pages.shape[-3]
    pos = _norm_scalar_vec(pos, b)
    win = _norm_scalar_vec(window, b, fill=s_logical + 1)
    hw = jnp.asarray(hot_window, jnp.int32).reshape(-1)[:1]
    out = flash_decode_gqa_paged_q8(qg, k_pages, v_pages, kq_pages,
                                    vq_pages, k_scales, v_scales, pos, win,
                                    block_tables, hw, layer, scale=scale,
                                    interpret=_interpret_default(interpret))
    out = out.reshape(b, h, dh).astype(v_pages.dtype)
    return out[:, None] if squeeze else out


def flash_decode_paged_mla_q8(q: jnp.ndarray, c_pages: jnp.ndarray,
                              cq_pages: jnp.ndarray, c_scales: jnp.ndarray,
                              pos: jnp.ndarray, block_tables: jnp.ndarray,
                              hot_window: jnp.ndarray, *, r: int,
                              scale: float, window=None,
                              interpret=None, layer=None) -> jnp.ndarray:
    """Shape-flexible wrapper around :func:`flash_decode_mla_paged_q8`.

    q: (B, 1, H, r + d_rope) or (B, H, r + d_rope); c_pages fp +
    cq_pages int8: (P, page_size, r + d_rope); c_scales: (P, 1) or (P,);
    pos: scalar or (B,); block_tables: (B, W) int32; hot_window: int or
    (1,) int32; ``r``: static latent rank. With ``layer``, pools and
    scales are layer-stacked (a leading (L,) dim), read at ``layer``.

    Returns the latent-space attention output shaped like q with last dim
    ``r``, in f32 (the caller applies ``W_uv`` once and converts).
    """
    had_q_axis = q.ndim == 4
    if had_q_axis:
        assert q.shape[1] == 1, q.shape
    else:
        q = q[:, None]
    b = q.shape[0]
    s_logical = block_tables.shape[1] * c_pages.shape[-2]
    pos = _norm_scalar_vec(pos, b)
    win = _norm_scalar_vec(window, b, fill=s_logical + 1)
    hw = jnp.asarray(hot_window, jnp.int32).reshape(-1)[:1]
    cs = jnp.asarray(c_scales, jnp.float32).reshape(c_pages.shape[:-2]
                                                    + (1,))
    out = flash_decode_mla_paged_q8(q, c_pages, cq_pages, cs, pos, win,
                                    block_tables, hw, layer, scale=scale,
                                    r=r,
                                    interpret=_interpret_default(interpret))
    return out if had_q_axis else out[:, 0]


# ----------------------------------------------------------------------------
# chunked-prefill wrappers (q_len > 1 through the same paged harness)
# ----------------------------------------------------------------------------
def _chunk_scalars(offset, limit, window, b: int, c: int, s_logical: int):
    """Fetch-union scalars for a chunk of ``c`` query rows starting at
    ``offset``: pos = the chunk's last VALID position (clamped below
    ``limit`` so block-table indexing never walks past the prompt's
    pages), win = per-row window widened by (c - 1) so the live-block
    range covers the earliest row's reach."""
    offv = _norm_scalar_vec(offset, b)
    limv = _norm_scalar_vec(limit, b)
    posv = jnp.clip(limv - 1, offv, offv + c - 1)
    winv = _norm_scalar_vec(window, b, fill=s_logical + 1) + (c - 1)
    return offv, posv, winv


def flash_chunk_paged(q: jnp.ndarray, k_pages: jnp.ndarray,
                      v_pages: jnp.ndarray, offset, limit,
                      block_tables: jnp.ndarray, *, scale: float,
                      window=None, interpret=None,
                      layer=None) -> jnp.ndarray:
    """Chunked-prefill GQA attention over a paged KV pool: the chunk's C
    query tokens (absolute positions offset .. offset + C - 1) causally
    attend everything already written for the request, including the
    chunk's own rows (write the chunk to the pool FIRST, then call this).

    q: (B, C, H, dh); k/v_pages: (P, page_size, Hkv, dh), or
    layer-stacked (L, P, ...) read at ``layer``; offset/limit: scalar or
    (B,) — rows at positions >= limit are padding (their outputs are
    finite garbage; the caller discards them); block_tables: (B, W) int32.

    Returns (B, C, H, dh) in v_pages.dtype.
    """
    b, c, h, dh = q.shape
    hkv = k_pages.shape[-2]
    g = h // hkv
    # (B, C, Hkv, G, dh) -> (B, Hkv, C, G, dh) -> rows = C * G per KV head
    qg = q.reshape(b, c, hkv, g, dh).transpose(0, 2, 1, 3, 4) \
        .reshape(b, hkv, c * g, dh)
    s_logical = block_tables.shape[1] * k_pages.shape[-3]
    offv, posv, winv = _chunk_scalars(offset, limit, window, b, c, s_logical)
    out = flash_decode_gqa_paged(qg, k_pages, v_pages, posv, winv,
                                 block_tables, offv, layer, scale=scale,
                                 chunk=c,
                                 interpret=_interpret_default(interpret))
    out = out.reshape(b, hkv, c, g, dh).transpose(0, 2, 1, 3, 4) \
        .reshape(b, c, h, dh)
    return out.astype(v_pages.dtype)


def flash_chunk_paged_mla(q: jnp.ndarray, c_pages: jnp.ndarray, offset,
                          limit, block_tables: jnp.ndarray, *, r: int,
                          scale: float, window=None,
                          interpret=None, layer=None) -> jnp.ndarray:
    """Chunked-prefill absorbed-MLA attention over a paged latent pool:
    same contract as :func:`flash_chunk_paged` with the absorbed query
    layout of :func:`flash_decode_paged_mla`.

    q: (B, C, H, r + d_rope); c_pages: (P, page_size, r + d_rope), or
    layer-stacked (L, P, ...) read at ``layer``; offset/limit: scalar or
    (B,); block_tables: (B, W) int32.

    Returns (B, C, H, r) f32 (the caller applies ``W_uv`` once).
    """
    b, c, h, dk = q.shape
    qg = q.reshape(b, 1, c * h, dk)      # row i -> position off + i // H
    s_logical = block_tables.shape[1] * c_pages.shape[-2]
    offv, posv, winv = _chunk_scalars(offset, limit, window, b, c, s_logical)
    out = flash_decode_mla_paged(qg, c_pages, posv, winv, block_tables,
                                 offv, layer, scale=scale, r=r, chunk=c,
                                 interpret=_interpret_default(interpret))
    return out.reshape(b, c, h, r)
