"""Batched decode serving driver: prefill a batch of prompts, then greedy
decode step-by-step with a persistent KV cache, all through the jitted
serve steps (same code path the decode dry-run cells lower).

Three serving shapes:

  * lock-step (default): every request at the same position, scalar ``pos``;
  * ragged (``--ragged``): per-request prompt lengths, a (B,) ``pos``
    vector, per-request last-logit gather at prefill — one jit'd decode
    step serving requests at heterogeneous positions. SSM/hybrid configs
    ride the same padded prefill: mamba layers mask the padded steps' dt
    to 0 (``models/ssm.py``), so the recurrent state snapshot equals the
    unpadded prompt's;
  * continuous (``--continuous``): a stream of heterogeneous-length
    requests over a fixed number of decode *slots* backed by a paged KV
    cache (``runtime/kv_cache.py``) — admit-on-release, per-slot pos,
    page-granular cache growth, eviction on EOS/length, preempt-and-requeue
    when the pool runs dry. One jit'd prefill (admission) and one jit'd
    decode step serve the whole stream with no recompilation across steps.
    SSM/hybrid configs serve through the same loop: their per-slot
    recurrent state (``runtime.layouts.RecurrentLayout``) is reset on
    admit/evict/preempt and recomputed on re-admission, while the page
    allocator keeps doing virtual sequence-length accounting (admission
    control, preemption) even when no attention pool exists.

``--attn-impl flash`` routes the decode cache read through the fused
Pallas flash-decode kernel (``kernels/flash_decode.py``) instead of the
einsum oracle; under ``--continuous`` this is the scalar-prefetch paged
kernel, so dead cache tiles are neither computed nor fetched. MLA archs
(deepseek-v3) serve ``--continuous`` through the paged *latent* pool
(r + d_rope per token) and the absorbed ``flash_decode_paged_mla`` kernel;
with ``--kv-quant`` cold latent pages stream as int8 through
``flash_decode_paged_mla_q8`` (quantized per-page absmax before the
W_uk/W_uv expansion). Which kernel serves which cache is the
``runtime/layouts.py`` registry's call — this driver never inspects cache
leaves.

``--sample`` (with ``--temperature`` / ``--top-k``) replaces greedy argmax
with temperature/top-k sampling.

``--kv-quant`` (continuous mode) turns on the hybrid-precision KV tier
(``runtime/kv_quant.py``): pages older than ``--hot-window`` are quantized
to int8 with per-page/per-head scales as they age out, and the decode read
mixes the tiers — the serving-side twin of the paper's ReRAM–SRAM split.

Robustness (continuous mode): ``--deadline`` / ``--retry-budget`` /
``--max-queue`` bound each request's life (terminal fail/reject events
instead of livelock or unbounded queues); every step a jit'd NaN/Inf
sentinel on the logits quarantines poisoned lanes (pages scrubbed, request
requeued, rest of the batch keeps decoding), and an injected kernel fault
degrades the stream to the layout's einsum oracle (a real kernel error
propagates). ``--chaos`` runs the
whole stream under ``runtime/faults.py``'s deterministic fault injector;
the serve report carries the structured event log either way.

Usage:
  python -m repro.launch.serve --arch stablelm-1.6b --batch 4 \
      --prompt-len 32 --gen-len 32 --mode w8a8 --ragged --attn-impl flash
  python -m repro.launch.serve --arch stablelm-1.6b --continuous \
      --slots 4 --requests 12 --page-size 8 --attn-impl flash
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time
from collections import Counter, deque
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core.yoco_linear import YocoConfig
from repro.core import yoco_linear
from repro.data import synthetic
from repro.distributed import sharding
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as model_mod
from repro.models.model import ModelRuntime
from repro.runtime import faults as faults_mod
from repro.runtime import kv_cache as kvc
from repro.runtime import kv_quant as kvq
from repro.runtime import layouts as layouts_mod
from repro.runtime import serve_step as SS
from repro.runtime import telemetry as telemetry_mod


def _on_tpu() -> bool:
    """The int8 matmuls take the Pallas kernels on TPU (``yoco_linear``'s
    ``use_pallas``) and the XLA int8 dot elsewhere."""
    return jax.default_backend() == 'tpu'


def _ragged_lens(batch: int, prompt_len: int) -> jnp.ndarray:
    """Deterministic per-request prompt lengths in [~half, prompt_len]."""
    lo = max(4, prompt_len // 2)
    lens = [prompt_len - (i * 3) % max(1, prompt_len - lo) for i in range(batch)]
    return jnp.array([max(lo, min(prompt_len, L)) for L in lens], jnp.int32)


def serve(arch: str, *, smoke: bool = True, batch: int = 4,
          prompt_len: int = 32, gen_len: int = 32, mode: str = 'bf16',
          prequantize: bool = False, seed: int = 0,
          attn_impl: str = 'einsum', ragged: bool = False,
          greedy: bool = True, temperature: float = 1.0, top_k: int = 0,
          quiet: bool = False) -> dict:
    cfg = configs.get(arch, smoke=smoke)
    if attn_impl == 'flash' and (cfg.mla is not None or cfg.family == 'ssm'):
        kind = 'MLA' if cfg.mla is not None else 'SSM'
        hint = ('MLA flash decode is the paged kernel — serve it with '
                '--continuous' if cfg.mla is not None
                else 'a pure-SSM decode has no attention cache to '
                     'flash-read; drop --attn-impl')
        raise ValueError(f'--attn-impl flash covers GQA decode on the '
                         f'contiguous cache; {arch} uses {kind} layers '
                         f'({hint})')
    yoco = YocoConfig(mode=mode, use_pallas=_on_tpu())
    rt = ModelRuntime(attn_impl=attn_impl)
    max_seq = prompt_len + gen_len

    params = model_mod.init_params(jax.random.key(seed), cfg)
    if prequantize:
        # load the network "into the array": int8 weights in situ
        params = yoco_linear.quantize_tree(params)
    dc = synthetic.for_arch(cfg, global_batch=batch, seq_len=prompt_len)
    prompts = synthetic.make_batch(dc, 0)['inputs']

    prefill_fn = jax.jit(SS.make_prefill_step(cfg, yoco, rt))
    decode_fn = jax.jit(SS.make_decode_step(cfg, yoco, rt, greedy=greedy,
                                            temperature=temperature,
                                            top_k=top_k),
                        donate_argnums=(3,))
    sample_key = jax.random.key(seed + 1)

    cache = model_mod.init_cache_tree(cfg, batch, max_seq)
    lens = _ragged_lens(batch, prompt_len) if ragged else None
    t0 = time.time()
    if ragged:
        # padded prompts; K/V beyond each request's length stay masked
        # (kpos > pos) and are overwritten as that request advances
        logits, cache = prefill_fn(params, dict(inputs=prompts), cache,
                                   last_pos=lens - 1)
    else:
        logits, cache = prefill_fn(params, dict(inputs=prompts), cache)
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0

    if greedy:
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    else:
        sample_key, sub = jax.random.split(sample_key)
        tok = SS.sample_tokens(logits, sub, temperature=temperature,
                               top_k=top_k)
    generated = [tok]
    pos_vec = lens if ragged else None
    t0 = time.time()
    for i in range(gen_len - 1):
        pos = (pos_vec + i) if ragged else jnp.int32(prompt_len + i)
        step_in = tok
        if cfg.input_kind == 'embeddings':
            # stub frontend: feed the token id as a (deterministic) embedding
            step_in = jax.nn.one_hot(tok % cfg.d_model, cfg.d_model,
                                     dtype=jnp.bfloat16)
        if greedy:
            tok, logits, cache = decode_fn(params, step_in, pos, cache)
        else:
            sample_key, sub = jax.random.split(sample_key)
            tok, logits, cache = decode_fn(params, step_in, pos, cache, sub)
        generated.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t0
    toks = jnp.stack(generated, axis=1)
    out = dict(
        prefill_s=round(t_prefill, 4),
        decode_s=round(t_decode, 4),
        tokens_per_s=round(batch * (gen_len - 1) / max(t_decode, 1e-9), 1),
        generated_shape=list(toks.shape),
        sample=[int(x) for x in jnp.ravel(toks)[:8]],
        attn_impl=attn_impl,
        ragged=bool(ragged),
    )
    if ragged:
        out['prompt_lens'] = [int(x) for x in lens]
    if not quiet:
        print(json.dumps(out))
    return out


# ----------------------------------------------------------------------------
# continuous batching over a paged KV cache
# ----------------------------------------------------------------------------
@dataclasses.dataclass
class Request:
    """One serving request: a prompt and a generation budget."""
    rid: int
    prompt: np.ndarray          # (plen,) int32, unpadded
    target_gen: int             # generation budget ("EOS" for synthetic runs)
    ttl_steps: Optional[int] = None   # deadline in scheduler steps from
                                      # submission (None: no deadline)


@dataclasses.dataclass
class _SlotState:
    req: Request
    pos: int                    # absolute position the next decode writes at
    tokens: List[int]
    admit_seq: int              # admission order (preemption picks youngest)


class ContinuousScheduler:
    """Admit-on-release continuous batching over ``slots`` decode lanes.

    Contract (mirrored in ROADMAP.md for the MLA follow-up):

    * **admit**: a pending request takes a free slot iff the pool can cover
      its padded prompt (``blocks_for(prompt_pad)`` pages, all-or-nothing).
      Admission runs the jit'd paged prefill (batch=1, fixed padded length,
      block-table row as the write map) and seeds the slot with the first
      sampled/greedy token at ``pos = plen``.
    * **grow**: before every decode step each active slot is ``ensure``d a
      page for the position it is about to write. If the pool is dry, the
      *youngest* active request is preempted — pages released, request
      requeued at the front of the pending queue (recompute-style
      preemption, no state checkpoint).
    * **evict**: a slot is released (pages back to the free list, table row
      reset to the garbage page) when its request emits ``eos_id`` or
      exhausts its generation budget; the freed slot admits on the next
      loop turn.
    * idle slots decode at ``pos=0`` against the garbage page and their
      outputs are discarded — the decode step's shapes never change, so
      nothing recompiles across steps.
    * **recurrent state** (SSM/hybrid configs): evict and preempt mark the
      slot in :attr:`dirty_slots`; the driver zeroes those rows
      (``runtime.layouts.reset_state_slots``) before the next decode step,
      so idle lanes decode against zeroed state, and admission resets the
      slot again before the prefill seeds it (recompute-style preemption —
      the state is never checkpointed, only re-derived from the prompt).
    * **age-out** (``hot_window`` set, the kv_quant tier): after admission
      and after growth, :meth:`aged_out_pages` lists the pages that just
      left the hot window — the driver quantizes exactly those into the
      int8 tier before the decode step reads them as cold.

    Robustness contract (PR 7; chaos-tested in tests/test_chaos_serve.py):

    * **terminal accounting**: every submitted request ends in exactly one
      of ``completed`` / ``failed`` / ``rejected`` / ``cancelled``, with a
      matching terminal event in :attr:`events` —
      ``faults.EventLog.terminal_accounting`` audits this on every run.
    * **deadline**: a request with ``ttl_steps`` set fails terminally
      (reason ``deadline``) once that many scheduler steps pass since
      submission, whether it is still queued or already decoding —
      :meth:`begin_step` expires it before admissions so it can't consume
      pool pages it can never finish with.
    * **retry budget**: every preemption/quarantine requeue counts against
      ``retry_budget``; past it the request fails terminally (reason
      ``retry_budget``) instead of livelocking at the queue front.
      ``max_queue_age`` (steps spent pending) closes the same hole for
      requests that are never even admitted.
    * **backpressure**: ``max_queue`` caps the pending queue; over-cap
      submissions are rejected (reason ``queue_full``), as are prompts the
      table can't hold (``oversized_prompt``/``empty_prompt``) or with
      out-of-vocab ids (``garbage_prompt``, when ``vocab_size`` is set).
    * **self-preemption guard**: growing a lane never preempts that lane
      while any other lane is live; the grower yields itself only as the
      last resort (and the retry budget then bounds the cycle).
    * **quarantine**: a lane whose logits go non-finite is released and
      requeued (recompute re-derives its state from the prompt, so the
      retry is lossless), and its physical pages are handed back for
      scrubbing before the free list can reallocate them.
    """

    def __init__(self, kv: kvc.PagedKVCache, *, prompt_pad: int,
                 eos_id: Optional[int] = None,
                 hot_window: Optional[int] = None,
                 retry_budget: Optional[int] = None,
                 max_queue: Optional[int] = None,
                 max_queue_age: Optional[int] = None,
                 vocab_size: Optional[int] = None,
                 events: Optional[faults_mod.EventLog] = None):
        if kv.blocks_for(prompt_pad) > kv.max_blocks:
            # no amount of waiting fixes a table that can't hold the
            # prompt — reject at construction instead of silently
            # truncating (or stalling) at admission time
            raise ValueError(
                f'padded prompt ({prompt_pad} positions, '
                f'{kv.blocks_for(prompt_pad)} blocks) exceeds the '
                f'block-table width ({kv.max_blocks} blocks * '
                f'{kv.page_size} positions); size max_blocks to the '
                f'longest admissible sequence')
        self.kv = kv
        self.prompt_pad = prompt_pad
        self.eos_id = eos_id
        self.retry_budget = retry_budget
        self.max_queue = max_queue
        self.max_queue_age = max_queue_age
        self.vocab_size = vocab_size
        self.events = events if events is not None else faults_mod.EventLog()
        self.pending: deque = deque()
        self.active: dict = {}                 # slot -> _SlotState
        self.free_slots = list(range(kv.slots - 1, -1, -1))
        self._admit_seq = 0
        self.completed: List[_SlotState] = []
        self.failed: List[Request] = []
        self.rejected: List[Request] = []
        self.cancelled: List[Request] = []
        self.n_preempted = 0
        self.n_quarantined = 0
        self.dirty_slots: List[int] = []       # recurrent rows to zero
        self.step_no = 0
        self._retries: dict = {}               # rid -> requeue count
        self._deadline_at: dict = {}           # rid -> step it expires at
        self._queue_age: dict = {}             # rid -> steps spent pending
        self.tier = (kvq.KVTierTracker(hot_window, kv.page_size)
                     if hot_window is not None else None)

    # -- terminal bookkeeping ------------------------------------------------
    _TERMINAL_LIST = dict(fail='failed', reject='rejected',
                          cancel='cancelled')

    def _terminal(self, req: Request, kind: str, **detail) -> None:
        getattr(self, self._TERMINAL_LIST[kind]).append(req)
        self._forget(req.rid)
        self.events.emit(kind, step=self.step_no, rid=req.rid, **detail)

    def _forget(self, rid: int) -> None:
        self._retries.pop(rid, None)
        self._deadline_at.pop(rid, None)
        self._queue_age.pop(rid, None)

    def _release_slot(self, slot: int, *, reason: str) -> _SlotState:
        """Mechanical slot teardown shared by every eviction path: pages
        back to the free list, slot freed, recurrent rows marked dirty,
        tier tracker reset — plus the ``evict`` event naming why."""
        st = self.active.pop(slot)
        self.kv.release(slot)
        self.free_slots.append(slot)
        self.dirty_slots.append(slot)
        if self.tier is not None:
            self.tier.reset(slot)
        self.events.emit('evict', step=self.step_no, rid=st.req.rid,
                         slot=slot, reason=reason, pos=st.pos)
        return st

    def _expired(self, rid: int) -> bool:
        at = self._deadline_at.get(rid)
        return at is not None and self.step_no >= at

    def begin_step(self, step: int) -> None:
        """Open scheduler step ``step``: age the pending queue and expire
        deadlines — pending and active alike — BEFORE admissions, so an
        expired request fails terminally instead of consuming pool pages
        it can never finish with."""
        self.step_no = step
        for req in list(self.pending):
            age = self._queue_age.get(req.rid, 0) + 1
            self._queue_age[req.rid] = age
            if self._expired(req.rid):
                self.pending.remove(req)
                self._terminal(req, 'fail', reason='deadline', waited=age)
            elif self.max_queue_age is not None and age > self.max_queue_age:
                self.pending.remove(req)
                self._terminal(req, 'fail', reason='aged_out', waited=age)
        for slot, st in list(self.active.items()):
            if self._expired(st.req.rid):
                self._release_slot(slot, reason='deadline')
                self._terminal(st.req, 'fail', reason='deadline',
                               pos=st.pos)

    def submit(self, req: Request) -> bool:
        """Validate and enqueue; returns False (with a terminal ``reject``
        event) on admission backpressure or a prompt no slot can serve."""
        self.events.emit('submit', step=self.step_no, rid=req.rid,
                         plen=len(req.prompt), gen=req.target_gen)
        if len(req.prompt) == 0:
            self._terminal(req, 'reject', reason='empty_prompt')
            return False
        if len(req.prompt) > self.prompt_pad:
            self._terminal(req, 'reject', reason='oversized_prompt',
                           plen=len(req.prompt),
                           prompt_pad=self.prompt_pad)
            return False
        if self.vocab_size is not None:
            ids = np.asarray(req.prompt)
            if int(ids.min()) < 0 or int(ids.max()) >= self.vocab_size:
                self._terminal(req, 'reject', reason='garbage_prompt',
                               vocab=self.vocab_size)
                return False
        if self.max_queue is not None and len(self.pending) >= self.max_queue:
            self._terminal(req, 'reject', reason='queue_full',
                           queued=len(self.pending))
            return False
        if req.ttl_steps is not None:
            self._deadline_at[req.rid] = self.step_no + req.ttl_steps
        self.pending.append(req)
        return True

    def cancel(self, rid: int) -> bool:
        """Mid-stream cancellation: drop the request wherever it is
        (pending queue or an active lane). Returns False for an unknown /
        already-terminal rid."""
        for req in self.pending:
            if req.rid == rid:
                self.pending.remove(req)
                self._terminal(req, 'cancel', where='pending')
                return True
        for slot, st in list(self.active.items()):
            if st.req.rid == rid:
                self._release_slot(slot, reason='cancel')
                self._terminal(st.req, 'cancel', where='active', pos=st.pos)
                return True
        return False

    @property
    def done(self) -> bool:
        return not self.pending and not self.active

    def try_admit(self, limit: Optional[int] = None):
        """Pop (request, slot, plan) triples that fit the pool right now;
        the caller runs the prefill the plan prescribes (COW copy, shared-
        suffix start) and then calls :meth:`seed`. The plan comes from
        ``PagedKVCache.admit_prompt``: with the prefix cache off it is
        always the historical full-prefill plan.

        ``limit`` caps the triples per call: the prefix-caching driver
        admits one at a time (prefill + seal between calls) so a burst of
        same-prompt requests shares the first tenant's freshly sealed
        pages instead of planning the whole wave against the pre-seal
        table."""
        admitted = []
        while self.pending and self.free_slots and \
                (limit is None or len(admitted) < limit):
            slot = self.free_slots[-1]
            req = self.pending[0]
            plan = self.kv.admit_prompt(slot, req.prompt,
                                        pad_positions=self.prompt_pad)
            if plan is None:
                break                           # pool dry: wait for release
            self.free_slots.pop()
            # admission resets the slot's recurrent rows itself, so a
            # pending dirty mark would only re-zero the freshly
            # prefilled state — drop it
            self.dirty_slots = [s for s in self.dirty_slots if s != slot]
            self.pending.popleft()
            self.events.emit('admit', step=self.step_no, rid=req.rid,
                             slot=slot,
                             retries=self._retries.get(req.rid, 0),
                             shared=plan['shared'],
                             prefill_start=plan['prefill_start'])
            admitted.append((req, slot, plan))
        return admitted

    def seed(self, req: Request, slot: int, first_token: int) -> None:
        self._admit_seq += 1
        st = _SlotState(req=req, pos=len(req.prompt),
                        tokens=[int(first_token)],
                        admit_seq=self._admit_seq)
        self.active[slot] = st
        self._maybe_finish(slot, int(first_token))

    def grow_for_decode(self) -> None:
        """Back every active slot's next write position with a page,
        preempting youngest-first when the pool runs dry."""
        for slot in sorted(self.active,
                           key=lambda s: self.active[s].admit_seq):
            st = self.active.get(slot)
            if st is None:
                continue            # preempted by an earlier iteration
            if st.pos // self.kv.page_size >= self.kv.max_blocks:
                # table-width exhaustion, not pool pressure: preemption
                # frees pages but can never widen the table — reject loudly
                raise ValueError(
                    f'request {st.req.rid} at pos {st.pos} exceeds the '
                    f'block-table width ({self.kv.max_blocks} blocks * '
                    f'{self.kv.page_size} positions); size max_blocks to '
                    f'the longest admissible sequence')
            while slot in self.active and not self.kv.ensure(slot, st.pos):
                self._preempt_youngest(exclude=slot)

    def _preempt_youngest(self, exclude: Optional[int] = None) -> None:
        """Preempt-and-requeue one active lane to free pages, youngest
        (by admission order) first — but never the lane currently being
        grown (``exclude``) while any other lane is live: a grower that
        preempts itself discards its own progress without relieving the
        pressure it was growing against. When the grower is the ONLY
        active lane it does yield itself as the last resort; the retry
        budget then turns a preempt/re-admit cycle that can never fit
        into a terminal failure instead of a livelock."""
        others = [s for s in self.active if s != exclude]
        victim = (max(others, key=lambda s: self.active[s].admit_seq)
                  if others else exclude)
        self._requeue(victim, kind='preempt')

    def force_preempt(self) -> bool:
        """Chaos hook (preemption storm): preempt the youngest active
        lane unconditionally. Returns False when nothing is active."""
        if not self.active:
            return False
        self._preempt_youngest()
        return True

    def quarantine(self, slot: int) -> List[int]:
        """Poisoned lane (non-finite logits): discard its generated
        tokens, release-and-requeue the request (recompute-style, so the
        retry is lossless; counts against the retry budget), and return
        the physical pages safe to scrub NOW, BEFORE the free list hands
        them to another request. Every page the lane held is retired from
        the prefix cache and marked scrub-before-reuse, but a sealed page
        another tenant still references is NEVER scrubbed in place — it
        stays immutable for its surviving owners and reaches the scrub
        queue (drained by the serve loop before admissions) only on its
        last release."""
        self.kv.defer_scrub(slot)
        self._requeue(slot, kind='quarantine')
        return self.kv.drain_scrub_queue()

    def _requeue(self, victim: int, *, kind: str) -> None:
        """Release ``victim`` and requeue its request at the queue front
        (recompute-style: generated tokens are discarded, the request
        re-prefills when pages free up) — unless its retry budget is
        spent, in which case it fails terminally."""
        st = self._release_slot(victim, reason=kind)
        if kind == 'preempt':
            self.n_preempted += 1
        else:
            self.n_quarantined += 1
        self.events.emit(kind, step=self.step_no, rid=st.req.rid,
                         slot=victim, pos=st.pos)
        r = self._retries.get(st.req.rid, 0) + 1
        self._retries[st.req.rid] = r
        if self.retry_budget is not None and r > self.retry_budget:
            self._terminal(st.req, 'fail', reason='retry_budget',
                           retries=r)
        else:
            self.pending.appendleft(st.req)
            self.events.emit('retry', step=self.step_no, rid=st.req.rid,
                             attempt=r)

    def aged_out(self) -> dict:
        """``slot -> physical pages`` that just crossed the hot-window
        boundary (kv_quant tier only). Call once after admissions and
        :meth:`grow_for_decode`, before the decode step — the step will
        read these pages as cold, so they must be int8 by then. NOTE: the
        tracker advances on this call, so the caller owns what happens to
        the pages (the chaos layer's drop-quant fault exploits exactly
        that: dropped pages stay zero in the int8 tier forever)."""
        if self.tier is None:
            return {}
        out: dict = {}
        for slot, st in self.active.items():
            pages = self.tier.aged_out(slot, st.pos, self.kv.tables[slot])
            if pages:
                out[slot] = pages
        return out

    def aged_out_pages(self) -> List[int]:
        """Flat-list view of :meth:`aged_out` (the tracker advances)."""
        return [p for ps_ in self.aged_out().values() for p in ps_]

    def step_vectors(self):
        """(token, pos) vectors for the jit'd decode step; idle slots get
        (0, 0) against the garbage page."""
        toks = np.zeros((self.kv.slots,), np.int32)
        pos = np.zeros((self.kv.slots,), np.int32)
        for slot, st in self.active.items():
            toks[slot] = st.tokens[-1]
            pos[slot] = st.pos
        return toks, pos

    def absorb(self, tok_np: np.ndarray) -> None:
        """Fold one decode step's tokens back into the slot states."""
        for slot in list(self.active):
            st = self.active[slot]
            tok = int(tok_np[slot])
            st.tokens.append(tok)
            st.pos += 1
            self._maybe_finish(slot, tok)

    def _maybe_finish(self, slot: int, tok: int) -> None:
        st = self.active.get(slot)
        if st is None:
            return
        hit_eos = self.eos_id is not None and tok == self.eos_id
        if hit_eos or len(st.tokens) >= st.req.target_gen:
            self._release_slot(slot, reason='finished')
            self.completed.append(st)
            self._forget(st.req.rid)
            self.events.emit('finish', step=self.step_no, rid=st.req.rid,
                             slot=slot, tokens=len(st.tokens))


def _ragged_stream(n_requests: int, prompt_len: int, gen_len: int,
                   prompts: np.ndarray) -> List[Request]:
    """Deterministic heterogeneous request stream: prompt lengths in
    [~half, prompt_len], generation budgets in [~half, gen_len]."""
    lo_p = max(4, prompt_len // 2)
    lo_g = max(2, gen_len // 2)
    reqs = []
    for i in range(n_requests):
        plen = lo_p + (i * 5) % max(1, prompt_len - lo_p + 1)
        glen = lo_g + (i * 3) % max(1, gen_len - lo_g + 1)
        reqs.append(Request(rid=i, prompt=np.asarray(prompts[i, :plen]),
                            target_gen=glen))
    return reqs


def serve_continuous(arch: str, *, smoke: bool = True, slots: int = 4,
                     n_requests: int = 8, prompt_len: int = 32,
                     gen_len: int = 32, page_size: int = 8,
                     num_pages: Optional[int] = None, mode: str = 'bf16',
                     prequantize: bool = False, seed: int = 0,
                     attn_impl: str = 'flash', tp: int = 1,
                     greedy: bool = True,
                     temperature: float = 1.0, top_k: int = 0,
                     eos_id: Optional[int] = None,
                     max_steps: Optional[int] = None,
                     kv_quant: bool = False, hot_window: int = 2,
                     prefix_cache: bool = False,
                     chunk_prefill: Optional[int] = None,
                     shared_prefix: Optional[int] = None,
                     request_stream: Optional[List[Request]] = None,
                     deadline: Optional[int] = None,
                     retry_budget: Optional[int] = 8,
                     max_queue: Optional[int] = None,
                     faults: Optional[faults_mod.FaultInjector] = None,
                     step_hook=None,
                     metrics: bool = True,
                     metrics_out: Optional[str] = None,
                     trace: Optional[str] = None,
                     registry=None,
                     oracle_check: bool = False,
                     quiet: bool = False) -> dict:
    """Serve a stream of heterogeneous-length requests end-to-end (admit,
    decode, evict, re-admit) under one jit'd decode step.

    ``kv_quant=True`` enables the hybrid-precision KV tier
    (``runtime.kv_quant``): pages older than ``hot_window`` are quantized
    to int8 as they age out; decode reads mix the tiers per the hotness
    rule (``hot_window >= max_blocks`` keeps everything fp — bit-exact
    with ``kv_quant=False``).

    Robustness (PR 7): ``deadline`` sets every synthetic request's TTL in
    scheduler steps; ``retry_budget`` bounds preemption/quarantine
    requeues per request (None: unlimited — the pre-PR-7 livelockable
    behavior); ``max_queue`` caps the pending queue with explicit
    rejection. ``faults`` plugs in a ``runtime.faults.FaultInjector``
    whose faults the loop applies at the scheduler edges; every step the
    jit'd ``logits_finite`` sentinel quarantines lanes with non-finite
    logits (pages scrubbed, request requeued — the rest of the batch
    keeps decoding), and an injected kernel fault
    (``faults.InjectedKernelError``) under ``attn_impl='flash'`` degrades
    the stream to the layout's densify einsum oracle with a logged
    ``degrade`` event; any other kernel error propagates, so a kernel
    that fails on the device never hides behind the oracle.
    ``step_hook(sched, kv, cache)`` runs after every absorbed step (chaos
    tests audit allocator invariants through it). ``oracle_check=True``
    also runs the first decode step on the einsum oracle and reports the
    kernel step's logit error against it (max abs difference over max abs
    oracle logit, live lanes) as ``out['oracle_logit_err']``.

    Observability (PR 8): ``metrics=True`` (the default) threads
    ``runtime.telemetry.ServeTelemetry`` through the loop — request-span
    histograms (TTFT/ITL/queue-wait, derived from the timestamped event
    log), per-step scheduler/pool/tier gauges, and live hwmodel-priced
    energy/traffic counters; the report gains ``out['telemetry']`` (full
    snapshot) and ``out['telemetry_summary']``, and ``metrics_out``
    writes the snapshot to a file (``.prom`` suffix: Prometheus text
    exposition, else JSON). ``trace`` writes a Chrome-trace/Perfetto
    JSON of the run (one track per slot plus a scheduler track; loads in
    ui.perfetto.dev). Every loop phase is a ``serve.*`` span
    (``telemetry.Span``): a profiler annotation even with ``metrics=False``
    (about 1 us), which skips the rest — the overhead gate compares the
    two; the snapshot's ``slowest_steps`` holds the longest steps by
    phase. Terminal counts derive from ``EventLog.terminal_accounting()``
    (single source of truth), not parallel counters."""
    cfg = configs.get(arch, smoke=smoke)
    # routing table (pinned by tests/test_serve_continuous.py): every token
    # family serves — MLA pages its latent pool through the same block
    # tables as GQA, and SSM/hybrid recurrent state rides the slot ops of
    # runtime.layouts.RecurrentLayout (reset on admit/evict/preempt,
    # recomputed on re-admission). Only non-token frontends stay blocked.
    if cfg.input_kind != 'tokens':
        raise ValueError(f'--continuous schedules token streams; {arch} '
                         f'has input_kind={cfg.input_kind} (the stubbed '
                         f'frontend cannot requeue/re-prefill non-token '
                         f'prompts)')
    if kv_quant and cfg.family == 'ssm':
        raise ValueError(f'--kv-quant tiers paged attention KV; {arch} is '
                         f'family=ssm with recurrent state only (no int8 '
                         f'tier — drop --kv-quant)')
    if (prefix_cache or chunk_prefill is not None) and \
            (cfg.family == 'ssm' or cfg.hybrid_group):
        # recurrent state folds the WHOLE prompt into one snapshot — there
        # is no per-position cache to share or to prefill a suffix of
        flag = '--prefix-cache' if prefix_cache else '--chunk-prefill'
        raise ValueError(f'{flag} needs random-access paged attention '
                         f'state; {arch} (family={cfg.family}, '
                         f'hybrid_group={cfg.hybrid_group}) carries '
                         f'recurrent state that must see every prompt '
                         f'position')
    mesh = None
    if tp > 1:
        # head-parallel tensor parallelism over a 1-D 'model' mesh: the
        # attention projections and the paged KV pools shard by head, the
        # scheduler/allocator stay host-global, and every jit'd step runs
        # under shard_map with exactly one collective per layer (the
        # head all-gather before wo). Token streams are bit-identical to
        # the single-device run — see runtime/serve_step.py tp_* builders.
        sharding.validate_serve_tp(cfg, tp)
        devs = jax.devices()
        if len(devs) < tp:
            raise ValueError(
                f'--tp {tp} needs {tp} devices; {len(devs)} visible '
                f'(CPU: set XLA_FLAGS=--xla_force_host_platform_'
                f'device_count={tp} before importing jax)')
        mesh = jax.sharding.Mesh(np.asarray(devs[:tp]), ('model',))
    yoco = YocoConfig(mode=mode, use_pallas=_on_tpu())
    rt = ModelRuntime(attn_impl=attn_impl)
    max_seq = prompt_len + gen_len
    max_blocks = -(-max_seq // page_size)
    if num_pages is None:
        num_pages = 1 + slots * max_blocks      # garbage page + full lanes
    if max_blocks > num_pages - 1:
        # one lane must always be able to run to completion — a pool that
        # can't hold a full sequence livelocks in preempt/re-prefill cycles
        raise ValueError(f'pool too small: a full {max_seq}-token sequence '
                         f'needs {max_blocks} pages, pool has '
                         f'{num_pages - 1} allocatable')
    kv = kvc.PagedKVCache(num_pages, page_size, max_blocks, slots,
                          prefix_cache=prefix_cache)
    events = faults_mod.EventLog()
    telem = None
    if metrics or trace:
        telem = telemetry_mod.ServeTelemetry(
            cfg, slots=slots, page_size=page_size, kv_quant=kv_quant,
            hot_window=hot_window, tp=tp, metrics=metrics,
            trace_path=trace, registry=registry)
        telem.attach(events)
    injector = faults
    sched = ContinuousScheduler(kv, prompt_pad=prompt_len, eos_id=eos_id,
                                hot_window=hot_window if kv_quant else None,
                                retry_budget=retry_budget,
                                max_queue=max_queue,
                                vocab_size=cfg.vocab_size, events=events)

    params = model_mod.init_params(jax.random.key(seed), cfg)
    if prequantize:
        params = yoco_linear.quantize_tree(params)
    dc = synthetic.for_arch(cfg, global_batch=max(n_requests, 1),
                            seq_len=prompt_len)
    prompts = np.asarray(synthetic.make_batch(dc, 0)['inputs'])
    if shared_prefix:
        # every synthetic prompt opens with the same "system prompt": the
        # stream that makes --prefix-cache demonstrable from the CLI
        prompts = prompts.copy()
        prompts[:, :shared_prefix] = prompts[0, :shared_prefix]
    stream = (request_stream if request_stream is not None
              else _ragged_stream(n_requests, prompt_len, gen_len, prompts))
    if request_stream is not None:
        n_requests = len(request_stream)
    for req in stream:
        req.ttl_steps = deadline
        if injector is not None:
            mangled = injector.mangle(req, prompt_pad=prompt_len,
                                      vocab=cfg.vocab_size)
            if mangled is not req:
                events.emit('fault', step=0, rid=req.rid,
                            fault='mangle_prompt',
                            plen=len(mangled.prompt))
                req = mangled
        sched.submit(req)

    cache = model_mod.init_paged_cache_tree(
        cfg, slots, num_pages=num_pages, page_size=page_size,
        max_blocks=max_blocks, kv_dtype='int8' if kv_quant else None,
        hot_window=hot_window)
    if mesh is not None:
        # place the weights and pools once: head-sharded leaves split on
        # their head axis, everything else (block tables, MLA latent
        # pools, wo/MLP/embed) replicated. The jit'd walkers (quantize/
        # scrub/COW/tail-zero) need no TP variants — GSPMD propagates
        # these shardings through their gather/scatter bodies unchanged.
        pspecs, cspecs = SS.serve_tp_specs(params, cache)
        params = jax.device_put(params, sharding.to_shardings(mesh, pspecs))
        cache = jax.device_put(cache, sharding.to_shardings(mesh, cspecs))
    # one jit'd shape: aged-out page lists are chunked to max_blocks wide
    # and padded with the garbage page (quantizing page 0 is harmless)
    quantize_fn = jax.jit(kvq.quantize_tree_pages, donate_argnums=(0,))
    n_pages_quantized = 0
    n_pages_quant_dropped = 0

    def in_page_chunks(fn, cache, pages):
        """Apply a (cache, (max_blocks,) page-vector) jit'd op over an
        arbitrary-length page list at one compiled shape (garbage-padded)."""
        while pages:
            chunk, pages = pages[:max_blocks], pages[max_blocks:]
            idx = np.zeros((max_blocks,), np.int32)
            idx[:len(chunk)] = chunk
            cache = fn(cache, jnp.asarray(idx))
        return cache

    def quantize_aged_out(cache):
        nonlocal n_pages_quantized, n_pages_quant_dropped
        by_slot = sched.aged_out()
        pages = [p for ps_ in by_slot.values() for p in ps_]
        if prefix_cache:
            # quantize-once-per-page under sharing: a sealed page ages out
            # of EVERY owner's hot window, but its int8 twin is content-
            # addressed like the page itself — quantize it the first time
            # only (release/eviction clears the mark, so a recycled page
            # re-quantizes for its next tenant). dict.fromkeys dedupes
            # within the step too: a burst-admitted prefix ages out of
            # every owner's aligned hot window on the SAME step
            pages = [p for p in dict.fromkeys(pages)
                     if p not in kv.quantized_pages]
        if pages and injector is not None and injector.drop_quant_now():
            # the tier tracker already advanced: these pages stay zero in
            # the int8 tier forever, so the affected requests' outputs
            # are legitimately altered — mark them touched (parity gates
            # exclude them) instead of pretending the fault didn't land
            rids = sorted(sched.active[s].req.rid for s in by_slot)
            injector.touched.update(rids)
            events.emit('fault', step=sched.step_no, fault='drop_quant',
                        pages=len(pages), rids=rids)
            n_pages_quant_dropped += len(pages)
            return cache
        n_pages_quantized += len(pages)
        if prefix_cache:
            kv.quantized_pages.update(pages)
        return in_page_chunks(quantize_fn, cache, pages)

    has_recurrent = cfg.family == 'ssm' or bool(cfg.hybrid_group)
    has_pool = cfg.family != 'ssm'      # pure-SSM trees carry no fp pool

    # chaos-layer device ops, compiled lazily on first fault so the happy
    # path pays nothing
    _chaos_fns: dict = {}

    def scrub_pages(cache, pages):
        """Zero a quarantined lane's pages across every per-page leaf —
        a NaN row surviving in the pool would poison the next tenant
        (additive masks keep NaN: NaN + -inf = NaN)."""
        if not pages or cfg.family == 'ssm':
            return cache     # pure-SSM trees have no pool to scrub
        if 'scrub' not in _chaos_fns:
            _chaos_fns['scrub'] = jax.jit(layouts_mod.scrub_tree_pages,
                                          donate_argnums=(0,))
        return in_page_chunks(_chaos_fns['scrub'], cache, pages)

    def poison_page_op(cache, page):
        if 'poison' not in _chaos_fns:
            _chaos_fns['poison'] = jax.jit(layouts_mod.poison_tree_pages,
                                           donate_argnums=(0,))
        return _chaos_fns['poison'](cache, jnp.asarray([page], jnp.int32))

    if mesh is not None:
        prefill_fn = jax.jit(
            SS.make_tp_prefill_step(cfg, yoco, mesh, params, cache,
                                    attn_impl=attn_impl),
            donate_argnums=(2,))
    else:
        prefill_fn = jax.jit(SS.make_prefill_step(cfg, yoco, rt),
                             donate_argnums=(2,))
    # chunked prefill: prefix-cache hits MUST take it (a monolithic padded
    # prefill would rewrite the shared pages it just acquired); misses take
    # it only when --chunk-prefill asks for admission/decode interleaving.
    # One chunk width per run = one extra jit signature.
    chunk_c = max(1, chunk_prefill if chunk_prefill is not None
                  else page_size)
    chunk_fn = None
    if prefix_cache or chunk_prefill is not None:
        if mesh is not None:
            chunk_fn = jax.jit(
                SS.make_tp_chunk_prefill_step(cfg, yoco, mesh, params,
                                              cache, attn_impl=attn_impl),
                donate_argnums=(4,))
        else:
            chunk_fn = jax.jit(SS.make_chunk_prefill_step(cfg, yoco, rt),
                               donate_argnums=(4,))
    cow_fn = (jax.jit(layouts_mod.copy_tree_pages, donate_argnums=(0,))
              if prefix_cache else None)
    tail_fn = (jax.jit(layouts_mod.zero_tree_tail, donate_argnums=(0,))
               if has_pool else None)

    def run_prefill(part, req, slot, plan):
        """Admission prefill over the slot-sliced tree ``part``, following
        the allocator's plan: COW-split the boundary page, compute only
        [prefill_start, plen) (chunked when the plan or --chunk-prefill
        demands it), then zero the padded tail rows of the last owned page
        so no stale bytes of a previous tenant survive into state that
        :meth:`PagedKVCache.seal_slot` is about to publish."""
        plen = len(req.prompt)
        if plan['cow'] is not None:
            src, dst = plan['cow']
            part = cow_fn(part, jnp.asarray(src, jnp.int32),
                          jnp.asarray(dst, jnp.int32))
        if chunk_fn is not None and (plan['hit']
                                     or chunk_prefill is not None):
            lim = jnp.asarray([plen], jnp.int32)
            logits = None
            for off in range(plan['prefill_start'], plen, chunk_c):
                ck = np.zeros((1, chunk_c), np.int32)
                seg = req.prompt[off:off + chunk_c]
                ck[0, :len(seg)] = seg
                logits, part = chunk_fn(params,
                                        dict(inputs=jnp.asarray(ck)),
                                        jnp.asarray([off], jnp.int32),
                                        lim, part)
        else:
            pad = np.zeros((prompt_len,), np.int32)
            pad[:plen] = req.prompt
            logits, part = prefill_fn(params,
                                      dict(inputs=jnp.asarray(pad[None])),
                                      part, jnp.asarray([plen - 1]))
        if tail_fn is not None:
            stop = int(kv.counts[slot]) * page_size
            if plen < stop:
                part = tail_fn(part, jnp.asarray(kv.tables[slot]),
                               jnp.asarray(plen, jnp.int32),
                               jnp.asarray(stop, jnp.int32))
        return logits, part

    def build_decode(impl, donate=(3,)):
        if mesh is not None:
            # the flash->einsum degrade path rebuilds THROUGH this too:
            # a TP stream degrades to the TP einsum oracle, never back to
            # a single-device step (the pools are already head-sharded)
            return jax.jit(
                SS.make_tp_decode_step(cfg, yoco, mesh, params, cache,
                                       attn_impl=impl, greedy=greedy,
                                       temperature=temperature,
                                       top_k=top_k),
                donate_argnums=donate)
        return jax.jit(
            SS.make_decode_step(cfg, yoco, ModelRuntime(attn_impl=impl),
                                greedy=greedy, temperature=temperature,
                                top_k=top_k),
            donate_argnums=donate)

    attn_impl_live = attn_impl
    decode_fn = build_decode(attn_impl_live)
    _decode_fns = [decode_fn]    # degrade rebuilds append here
    # oracle check: the first decode step also runs on the einsum oracle
    # (no donation, so the kernel step still reads the same cache)
    oracle_fn = build_decode('einsum', donate=()) if oracle_check else None
    oracle_err = None
    sentinel_fn = jax.jit(SS.logits_health)
    sample_key = jax.random.key(seed + 1)

    def call_decode(cache, toks_j, pos_j):
        nonlocal sample_key
        if greedy:
            return decode_fn(params, toks_j, pos_j, cache)
        sample_key, sub = jax.random.split(sample_key)
        return decode_fn(params, toks_j, pos_j, cache, sub)

    def first_token(logits):
        nonlocal sample_key
        if greedy:
            return int(jnp.argmax(logits, axis=-1)[0])
        sample_key, sub = jax.random.split(sample_key)
        return int(SS.sample_tokens(logits, sub, temperature=temperature,
                                    top_k=top_k)[0])

    steps = busy_slot_steps = 0
    peak_pages = 0
    t_prefill = 0.0
    # each loop phase is a profiler annotation, and telemetry when on
    span = telem.span if telem is not None else telemetry_mod.span

    def _admit_one(req, slot, plan):
        """One admission, every layout: zero the slot's recurrent rows (a
        fresh request must not see the evicted tenant's state), prefill a
        batch-1 view — recurrent leaves sliced to the slot (a copy, so the
        full tree survives the donated prefill), paged pools by reference
        — then fold the prefilled state back in. On attention-only trees
        the slice/merge walks are the identity. ``run_prefill`` follows
        the allocator's plan (COW copy, shared-suffix start, padded-tail
        zeroing); sealing right after prefill lets the NEXT admission of
        this same step share the pages just published."""
        nonlocal cache, t_prefill
        with span('serve.prefill') as sp:
            cache = layouts_mod.reset_state_slots(cache, [slot])
            part = layouts_mod.slice_state_slot(
                kvc.with_block_tables(cache, kv.tables[slot:slot + 1]),
                slot)
            logits, part = run_prefill(part, req, slot, plan)
            cache = layouts_mod.merge_state_slot(cache, part, slot)
            kv.seal_slot(slot, req.prompt)
        t_prefill += sp.seconds
        # the admit event predates the prefill; attach the measured
        # duration so spans (TTFT) derive from the log alone
        events.annotate_last('admit', req.rid, prefill_s=sp.seconds)
        with span('serve.wait', on='prefill'):
            tok0 = first_token(logits)
        sched.seed(req, slot, tok0)

    t0 = time.time()
    limit = max_steps if max_steps is not None else \
        n_requests * (prompt_len + gen_len) * 4 + 64
    while not sched.done and steps < limit:
        with span('serve.step', step=steps):
            sched.begin_step(steps)
            if injector is not None:
                injector.begin_step(steps)
                # pool squeeze: the injector holds free pages hostage;
                # the scheduler sees a smaller pool and must queue/preempt
                want = injector.squeeze_pages()
                delta = want - len(kv.reserved)
                if delta > 0:
                    if kv.reserve_pages(delta):
                        events.emit('fault', step=steps,
                                    fault='pool_squeeze',
                                    held=len(kv.reserved))
                elif delta < 0:
                    kv.unreserve_pages(-delta)
                # mid-stream cancellation of a live (pending or active) rid
                want_cancel = injector.cancel_now()
                if want_cancel:
                    live = sorted({st.req.rid for st in sched.active.values()}
                                  | {r.rid for r in sched.pending})
                    rid = want_cancel if not isinstance(want_cancel, bool) \
                        else (injector.pick(live) if live else None)
                    if rid is not None:
                        sched.cancel(rid)
            # --- admit on release -------------------------------------
            # deferred scrubs: pages a quarantined tenant shared with a
            # then-live lane reach the queue on that lane's own release —
            # zero them before the free list can hand them out again
            deferred = kv.drain_scrub_queue() if has_pool else None
            if deferred:
                with span('serve.scrub', pages=len(deferred)):
                    cache = scrub_pages(cache, deferred)
            # prefix caching admits one at a time (prefill + seal between
            # admissions) so same-step bursts share the first tenant's pages
            admit_limit = 1 if prefix_cache else None
            while True:
                batch = sched.try_admit(limit=admit_limit)
                if not batch:
                    break
                for req, slot, plan in batch:
                    with span('serve.admit', rid=req.rid, slot=slot,
                              plen=len(req.prompt)):
                        _admit_one(req, slot, plan)
                if admit_limit is None:
                    break
            if sched.done:
                break
            if injector is not None:
                # preemption storm: force-preempt lanes (fresh ones too)
                for _ in range(injector.storm_count()):
                    if sched.force_preempt():
                        events.emit('fault', step=steps,
                                    fault='preempt_storm')
            # --- grow + decode one step over every lane -----------------
            with span('serve.dispatch') as dispatch:
                sched.grow_for_decode()
                if has_recurrent and sched.dirty_slots:
                    # evicted/preempted lanes decode against zeroed state
                    # until re-admission (constant shapes, no recompile)
                    cache = layouts_mod.reset_state_slots(
                        cache, sorted(set(sched.dirty_slots)))
                sched.dirty_slots.clear()
                if kv_quant:
                    # pages that just left the hot window become int8
                    # before the step reads them as cold (fresh ones too)
                    with span('serve.quantize') as sq:
                        quantized_before = n_pages_quantized
                        cache = quantize_aged_out(cache)
                        sq.note(pages=n_pages_quantized - quantized_before)
                if (injector is not None and has_pool and sched.active
                        and injector.poison_page_now()):
                    # NaN an owned fp pool page: the model of a corrupted
                    # in-memory read; the sentinel below must catch it
                    cand = [(s, int(p)) for s in sorted(sched.active)
                            for p in kv.tables[s, :int(kv.counts[s])]]
                    if cand:
                        slot, page = injector.pick(cand)
                        cache = poison_page_op(cache, page)
                        # a shared page poisons EVERY owner: each trips
                        # the sentinel; the first quarantine retires the
                        # page from the prefix table for later admissions
                        owners = (kv.owners_of(page) if prefix_cache
                                  else [slot])
                        events.emit('fault', step=steps,
                                    fault='poison_page', slot=slot,
                                    page=page, owners=owners,
                                    rid=sched.active[slot].req.rid)
                poison_slot = None
                if (injector is not None and sched.active
                        and injector.poison_logits_now()):
                    poison_slot = injector.pick(sorted(sched.active))
                    events.emit('fault', step=steps, fault='poison_logits',
                                slot=poison_slot,
                                rid=sched.active[poison_slot].req.rid)
                peak_pages = max(peak_pages, kv.used_pages)
                if telem is not None:
                    # gauges + hwmodel energy pricing over the step's
                    # actual batch composition (pos/tier state is final)
                    with span('serve.telemetry'):
                        telem.sample(sched, kv)
                toks, pos = sched.step_vectors()
                cache = kvc.with_block_tables(cache, kv.table_array())
                busy_slot_steps += len(sched.active)
                dispatch.note(lanes=len(sched.active))
                active_now = sorted(sched.active)
                if oracle_check and oracle_err is None and sched.active:
                    # the same step on the layout's einsum oracle, before
                    # the donating kernel step consumes the cache it reads
                    # (a sampled step's logits do not depend on its key)
                    ref_logits = oracle_fn(params, jnp.asarray(toks),
                                           jnp.asarray(pos), cache,
                                           *(() if greedy else
                                             (sample_key,)))[1]
                try:
                    if (injector is not None and attn_impl_live == 'flash'
                            and injector.kernel_fault_now()):
                        raise faults_mod.InjectedKernelError(
                            'chaos: simulated kernel-path validation '
                            'failure')
                    tok, logits, cache = call_decode(cache, jnp.asarray(toks),
                                                     jnp.asarray(pos))
                except faults_mod.InjectedKernelError as e:
                    # graceful degradation, exercised by the chaos layer
                    # only: a real kernel failure propagates instead of
                    # hiding the device. The injected failure fires before
                    # the call, so the cache is intact — rebuild the step
                    # on the layout's densify einsum oracle and retry
                    events.emit('degrade', step=steps, frm='flash',
                                to='einsum',
                                error=f'{type(e).__name__}: {str(e)[:160]}')
                    attn_impl_live = 'einsum'
                    with span('serve.degrade', frm='flash', to='einsum'):
                        decode_fn = build_decode('einsum')
                    _decode_fns.append(decode_fn)
                    tok, logits, cache = call_decode(cache, jnp.asarray(toks),
                                                     jnp.asarray(pos))
                if oracle_check and oracle_err is None and sched.active:
                    lanes = jnp.asarray(active_now)
                    got = logits[lanes].astype(jnp.float32)
                    want = ref_logits[lanes].astype(jnp.float32)
                    oracle_err = float(
                        jnp.max(jnp.abs(got - want))
                        / jnp.maximum(jnp.max(jnp.abs(want)), 1e-30))
                # --- integrity sentinel: quarantine non-finite lanes -----
                ok, logit_max = sentinel_fn(logits)
                if poison_slot is not None:
                    lg = np.asarray(logits, np.float32)
                    lg[poison_slot] = np.nan
                    ok, logit_max = sentinel_fn(jnp.asarray(lg))
            with span('serve.wait', on='decode'):
                ok = np.asarray(ok)
            with span('serve.absorb'):
                if telem is not None:
                    # device scalar handed over as-is; telemetry
                    # host-transfers it once at finish, never per step
                    telem.logits_gauge(logit_max)
                for slot in [s for s in sorted(sched.active) if not ok[s]]:
                    # quarantine BEFORE absorb: a poisoned lane must not
                    # finish on a garbage token (NaN argmax is id 0). The
                    # requeue is lossless (recompute from the prompt); the
                    # scrub keeps the poison from the page's next tenant.
                    pages = sched.quarantine(slot)
                    with span('serve.scrub', pages=len(pages)):
                        cache = scrub_pages(cache, pages)
                sched.absorb(np.asarray(tok))
            steps += 1
            if step_hook is not None:
                with span('serve.hook'):
                    step_hook(sched, kv, cache)
    jax.block_until_ready(jax.tree.leaves(cache)[0])
    wall = time.time() - t0
    if not sched.done:
        raise RuntimeError(f'continuous serve stalled after {steps} steps: '
                           f'{len(sched.pending)} pending, '
                           f'{len(sched.active)} active')

    outputs = {st.req.rid: st.tokens
               for st in sorted(sched.completed, key=lambda s: s.req.rid)}
    # the auditing contract: every submitted request reached exactly one
    # terminal state — raises on a leaked request, even outside tests.
    # The report's terminal counts are DERIVED from the audited log (one
    # source of truth), not recounted from scheduler lists.
    term = events.terminal_accounting()
    tcounts = Counter(term.values())
    evc = events.counts()
    out = dict(
        requests=n_requests,
        completed=tcounts.get('finish', 0),
        failed=tcounts.get('fail', 0),
        rejected=tcounts.get('reject', 0),
        cancelled=tcounts.get('cancel', 0),
        steps=steps,
        decode_tokens=busy_slot_steps,
        wall_s=round(wall, 4),
        prefill_s=round(t_prefill, 4),
        tokens_per_s=round(busy_slot_steps / max(wall - t_prefill, 1e-9), 1),
        slot_utilization=round(busy_slot_steps / max(steps * slots, 1), 3),
        peak_pages=peak_pages,
        total_pages=num_pages - 1,
        page_size=page_size,
        preempted=evc.get('preempt', 0),
        quarantined=evc.get('quarantine', 0),
        attn_impl=attn_impl,
        attn_impl_effective=attn_impl_live,
        tp=tp,
        kv_quant=bool(kv_quant),
        hot_window=hot_window if kv_quant else None,
        pages_quantized=n_pages_quantized,
        pages_quant_dropped=n_pages_quant_dropped,
        prefix=(dict(hits=kv.prefix_hits, misses=kv.prefix_misses,
                     evictions=kv.prefix_evictions,
                     cow_copies=kv.cow_copies,
                     cached_pages=kv.cached_pages,
                     shared_pages=kv.shared_pages)
                if prefix_cache else None),
        chunk_prefill=(chunk_c if (prefix_cache or chunk_prefill is not None)
                       else None),
        events=evc,
        faults=(dict(injector.counts) if injector is not None else None),
        # admit/evict churn must never retrace: idle slots keep the step
        # shapes constant, so exactly one decode compilation serves the run
        # (a degrade rebuild adds exactly one more, on the einsum oracle)
        decode_compilations=(sum(f._cache_size() for f in _decode_fns)
                            if hasattr(decode_fn, '_cache_size') else None),
        oracle_logit_err=oracle_err,
        out_lens={r: len(t) for r, t in outputs.items()},
        sample={r: t[:4] for r, t in list(outputs.items())[:4]},
    )
    snap = telem.finish(events) if telem is not None else None
    if snap is not None:
        out['telemetry_summary'] = telemetry_mod.summarize(snap)
    if not quiet:
        print(json.dumps(out))
    out['outputs'] = outputs
    out['event_log'] = events.records()
    out['terminal'] = term
    if snap is not None:
        out['telemetry'] = snap
        if metrics_out:
            with open(metrics_out, 'w') as f:
                if metrics_out.endswith('.prom'):
                    f.write(telem.reg.render_prometheus())
                else:
                    json.dump(snap, f, indent=1)
            out['metrics_out'] = metrics_out
    if telem is not None:
        trace_path = telem.close_trace()
        if trace_path is not None:
            out['trace'] = trace_path
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--arch', default='stablelm-1.6b')
    ap.add_argument('--smoke', action='store_true', default=True,
                    help='reduced-width config (the default)')
    ap.add_argument('--full', dest='smoke', action='store_false',
                    help="the arch's published widths")
    ap.add_argument('--batch', type=int, default=4)
    ap.add_argument('--prompt-len', type=int, default=32)
    ap.add_argument('--gen-len', type=int, default=32)
    ap.add_argument('--mode', default='bf16',
                    choices=['bf16', 'qat', 'w8a8', 'analog_sim'])
    ap.add_argument('--prequantize', action='store_true')
    ap.add_argument('--attn-impl', default=None,
                    choices=['einsum', 'flash'],
                    help='default: flash under --continuous (the paged '
                         'prefetch kernel), einsum otherwise')
    ap.add_argument('--ragged', action='store_true')
    ap.add_argument('--sample', action='store_true',
                    help='temperature/top-k sampling instead of greedy')
    ap.add_argument('--temperature', type=float, default=1.0)
    ap.add_argument('--top-k', type=int, default=0)
    ap.add_argument('--continuous', action='store_true',
                    help='continuous batching over a paged KV cache')
    ap.add_argument('--slots', type=int, default=4,
                    help='decode lanes (continuous mode)')
    ap.add_argument('--requests', type=int, default=8,
                    help='synthetic request-stream length (continuous mode)')
    ap.add_argument('--page-size', type=int, default=8)
    ap.add_argument('--num-pages', type=int, default=None,
                    help='pool size incl. garbage page; shrink to exercise '
                         'queueing/preemption')
    ap.add_argument('--eos-id', type=int, default=None)
    ap.add_argument('--tp', type=int, default=1,
                    help='continuous mode: head-parallel tensor '
                         'parallelism over a 1-D device mesh (attention '
                         'projections + paged KV pools shard by head; '
                         'token streams stay bit-identical to --tp 1). '
                         'On CPU, set XLA_FLAGS=--xla_force_host_'
                         'platform_device_count=N first')
    ap.add_argument('--kv-quant', action='store_true',
                    help='hybrid-precision KV tier (continuous mode): '
                         'int8 cold pages + fp hot window')
    ap.add_argument('--hot-window', type=int, default=2,
                    help='full-precision pages per request (>= 1; '
                         '>= max_blocks disables the int8 tier)')
    ap.add_argument('--prefix-cache', action='store_true',
                    help='continuous mode: refcounted sharing of sealed '
                         'full-block prompt pages across requests, with '
                         'copy-on-write at the shared/private boundary '
                         '(attention families only)')
    ap.add_argument('--chunk-prefill', type=int, default=None, metavar='C',
                    help='prefill prompts in C-token chunks through the '
                         'paged chunk kernel instead of one monolithic '
                         'padded call (implied for prefix-cache hits; '
                         'attention families only)')
    ap.add_argument('--shared-prefix', type=int, default=None, metavar='N',
                    help='give every synthetic request the same leading N '
                         'tokens (a shared system prompt) — pair with '
                         '--prefix-cache to observe hits')
    ap.add_argument('--deadline', type=int, default=None,
                    help='per-request TTL in scheduler steps (continuous '
                         'mode); expired requests fail terminally')
    ap.add_argument('--retry-budget', type=int, default=8,
                    help='preemption/quarantine requeues per request '
                         'before it fails terminally (continuous mode; '
                         '-1: unlimited, the livelockable pre-PR-7 '
                         'behavior)')
    ap.add_argument('--max-queue', type=int, default=None,
                    help='admission backpressure (continuous mode): '
                         'reject submissions past this pending-queue '
                         'depth')
    ap.add_argument('--chaos', action='store_true',
                    help='continuous mode: run under the default fault-'
                         'injection profile (runtime.faults.chaos_profile)')
    ap.add_argument('--chaos-seed', type=int, default=0)
    ap.add_argument('--metrics', action=argparse.BooleanOptionalAction,
                    default=True,
                    help='continuous mode: lifecycle/tier/energy metrics '
                         '(--no-metrics strips all instrumentation)')
    ap.add_argument('--metrics-out', default=None, metavar='FILE',
                    help='write the final metrics snapshot (.prom: '
                         'Prometheus text exposition, else JSON)')
    ap.add_argument('--trace', default=None, metavar='FILE',
                    help='write a Chrome-trace/Perfetto JSON of the run '
                         '(load at ui.perfetto.dev)')
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.continuous:
        injector = (faults_mod.FaultInjector(
            seed=args.chaos_seed, profile=faults_mod.chaos_profile())
            if args.chaos else None)
        serve_continuous(args.arch, smoke=args.smoke, slots=args.slots,
                         n_requests=args.requests,
                         prompt_len=args.prompt_len, gen_len=args.gen_len,
                         page_size=args.page_size, num_pages=args.num_pages,
                         mode=args.mode, prequantize=args.prequantize,
                         attn_impl=args.attn_impl or 'flash',
                         tp=args.tp, greedy=not args.sample,
                         temperature=args.temperature, top_k=args.top_k,
                         eos_id=args.eos_id, kv_quant=args.kv_quant,
                         hot_window=args.hot_window,
                         prefix_cache=args.prefix_cache,
                         chunk_prefill=args.chunk_prefill,
                         shared_prefix=args.shared_prefix,
                         deadline=args.deadline,
                         retry_budget=(None if args.retry_budget < 0
                                       else args.retry_budget),
                         max_queue=args.max_queue, faults=injector,
                         metrics=args.metrics,
                         metrics_out=args.metrics_out, trace=args.trace)
    else:
        serve(args.arch, smoke=args.smoke, batch=args.batch,
              prompt_len=args.prompt_len, gen_len=args.gen_len,
              mode=args.mode, prequantize=args.prequantize,
              attn_impl=args.attn_impl or 'einsum', ragged=args.ragged,
              greedy=not args.sample, temperature=args.temperature,
              top_k=args.top_k)


if __name__ == '__main__':
    main()
