"""Chaos tests: continuous serving under injected faults (pool squeezes,
preemption storms, NaN poisoning of pool pages and logits rows, dropped
quantize chunks, cancellations, kernel-path failures).

The gates mirror the PR 7 acceptance criteria: unfaulted requests decode
token-identically vs solo decode, poisoned lanes are quarantined and
retried without crashing the batch, ``PagedKVCache.check_invariants()``
holds after every step, and the event log accounts for every submitted
request's terminal state.

Run with ``make test-chaos`` (part of ``make check``)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.yoco_linear import YocoConfig
from repro.data import synthetic
from repro.launch import serve as SV
from repro.models import model as model_mod
from repro.models.model import ModelRuntime
from repro.runtime import faults
from repro.runtime import serve_step as SS

pytestmark = pytest.mark.chaos

ARCH = 'stablelm-1.6b'


# ----------------------------------------------------------------------------
# solo-decode oracle (same pattern as tests/test_serve_continuous.py)
# ----------------------------------------------------------------------------
@functools.lru_cache(maxsize=2)
def _reference_model(arch=ARCH):
    cfg = configs.get(arch, smoke=True)
    yoco, rt = YocoConfig(mode='bf16'), ModelRuntime()
    params = model_mod.init_params(jax.random.key(0), cfg)
    prefill = jax.jit(SS.make_prefill_step(cfg, yoco, rt))
    decode = jax.jit(SS.make_decode_step(cfg, yoco, rt))
    return cfg, params, prefill, decode


def _reference_tokens(req, prompt_len, gen_len, arch=ARCH):
    """Greedy-decode one request alone through the contiguous einsum path:
    the oracle every un-faulted continuous stream must reproduce."""
    cfg, params, prefill, decode = _reference_model(arch)
    cache = model_mod.init_cache_tree(cfg, 1, prompt_len + gen_len)
    pad = np.zeros((1, prompt_len), np.int32)
    pad[0, :len(req.prompt)] = req.prompt
    logits, cache = prefill(params, dict(inputs=jnp.asarray(pad)), cache,
                            jnp.asarray([len(req.prompt) - 1]))
    toks = [int(jnp.argmax(logits, -1)[0])]
    pos = len(req.prompt)
    while len(toks) < req.target_gen:
        t, _, cache = decode(params, jnp.asarray([toks[-1]], jnp.int32),
                             jnp.asarray([pos], jnp.int32), cache)
        toks.append(int(t[0]))
        pos += 1
    return toks


def _stream_requests(n, prompt_len, gen_len, arch=ARCH):
    cfg = configs.get(arch, smoke=True)
    dc = synthetic.for_arch(cfg, global_batch=n, seq_len=prompt_len)
    prompts = np.asarray(synthetic.make_batch(dc, 0)['inputs'])
    return SV._ragged_stream(n, prompt_len, gen_len, prompts)


def _assert_parity(out, rids, prompt_len, gen_len, arch=ARCH):
    reqs = {r.rid: r for r in _stream_requests(out['requests'], prompt_len,
                                               gen_len, arch)}
    for rid in rids:
        want = _reference_tokens(reqs[rid], prompt_len, gen_len, arch)
        assert out['outputs'][rid] == want, (rid, out['outputs'][rid], want)


def _invariant_hook(counter):
    def hook(sched, kv, cache):
        kv.check_invariants()
        counter[0] += 1
    return hook


KW = dict(slots=3, n_requests=6, prompt_len=16, gen_len=8, page_size=4,
          quiet=True)


# ----------------------------------------------------------------------------
# targeted fault -> recovery scenarios
# ----------------------------------------------------------------------------
def test_poisoned_logits_quarantined_and_retried_losslessly():
    """A NaN'd logits row quarantines exactly that lane; the recompute
    retry is lossless, so EVERY request still matches solo decode."""
    inj = faults.FaultInjector(seed=0, schedule=[(4, 'poison_logits', None),
                                                 (9, 'poison_logits', None)])
    audited = [0]
    out = SV.serve_continuous(ARCH, attn_impl='einsum', faults=inj,
                              step_hook=_invariant_hook(audited), **KW)
    assert out['completed'] == KW['n_requests']
    assert out['quarantined'] == 2
    assert out['events']['quarantine'] == 2
    assert audited[0] == out['steps']
    _assert_parity(out, range(KW['n_requests']), KW['prompt_len'],
                   KW['gen_len'])


def test_poisoned_pool_page_scrubbed_no_cross_request_leak():
    """NaN in an owned cache page poisons its lane's logits (the additive
    mask keeps NaN), the sentinel quarantines it, and the scrub keeps the
    released page from poisoning its NEXT tenant — so the whole stream
    still completes with solo-decode parity."""
    inj = faults.FaultInjector(seed=1, schedule=[(3, 'poison_page', None),
                                                 (7, 'poison_page', None)])
    audited = [0]
    out = SV.serve_continuous(ARCH, attn_impl='einsum', faults=inj,
                              step_hook=_invariant_hook(audited), **KW)
    assert out['completed'] == KW['n_requests']
    assert out['quarantined'] >= 1          # the poisoned lanes, only them
    assert out['faults']['poison_page'] == 2
    _assert_parity(out, range(KW['n_requests']), KW['prompt_len'],
                   KW['gen_len'])


def test_kernel_fault_degrades_to_einsum_with_parity():
    """A kernel-path failure mid-stream falls back to the layout's densify
    einsum oracle: one degrade event, one extra compilation, the stream
    finishes token-identical to solo decode."""
    inj = faults.FaultInjector(seed=0, schedule=[(5, 'kernel_fault', None)])
    out = SV.serve_continuous(ARCH, attn_impl='flash', faults=inj, **KW)
    assert out['attn_impl'] == 'flash'
    assert out['attn_impl_effective'] == 'einsum'
    assert out['events']['degrade'] == 1
    assert out['decode_compilations'] == 2   # flash once + einsum once
    assert out['completed'] == KW['n_requests']
    _assert_parity(out, range(KW['n_requests']), KW['prompt_len'],
                   KW['gen_len'])


def test_real_kernel_error_propagates(monkeypatch):
    """Only the chaos layer's injected fault degrades: a real exception
    on the kernel path propagates instead of hiding behind the oracle."""
    from repro.runtime import layouts

    def broken(cls, *a, **k):
        raise RuntimeError('kernel failed to compile')
    monkeypatch.setattr(layouts.PagedLayout, 'flash_decode',
                        classmethod(broken))
    with pytest.raises(RuntimeError, match='kernel failed to compile'):
        SV.serve_continuous(ARCH, attn_impl='flash', **KW)


def test_pool_squeeze_and_storm_recover_with_parity():
    """Held-hostage pages + forced preemption storms: pure recompute
    churn, so every request that completes is token-identical."""
    inj = faults.FaultInjector(
        seed=2,
        profile=faults.FaultProfile(squeeze_pages=4, squeeze_steps=4),
        schedule=[(2, 'pool_squeeze', None), (6, 'preempt_storm', 2),
                  (11, 'preempt_storm', 1)])
    audited = [0]
    out = SV.serve_continuous(ARCH, attn_impl='einsum', faults=inj,
                              step_hook=_invariant_hook(audited),
                              retry_budget=20, **KW)
    assert out['completed'] == KW['n_requests']
    assert out['preempted'] >= 3
    assert out['faults']['pool_squeeze'] == 1
    assert out['faults']['preempt_storm'] == 2
    _assert_parity(out, range(KW['n_requests']), KW['prompt_len'],
                   KW['gen_len'])


def test_drop_quant_marks_requests_touched():
    """A dropped quantize chunk is NOT recoverable (the tier tracker
    already advanced; the cold tier stays zero) — the injector must mark
    the affected rids touched so parity gates skip exactly them."""
    # rate 1.0 (not a scheduled step): drop-quant only consumes on steps
    # where a chunk actually ages out, so arm it every step
    inj = faults.FaultInjector(seed=0,
                               profile=faults.FaultProfile(drop_quant=1.0))
    out = SV.serve_continuous(ARCH, attn_impl='flash', kv_quant=True,
                              hot_window=1, faults=inj, **KW)
    assert out['completed'] == KW['n_requests']
    assert out['pages_quant_dropped'] > 0
    assert inj.touched                      # someone's cold tier is zero
    drop = [e for e in out['event_log'] if e.get('fault') == 'drop_quant']
    assert drop and set(drop[0]['rids']) <= set(inj.touched)


def test_mangled_prompts_rejected_stream_survives():
    inj = faults.FaultInjector(seed=0, schedule=[
        (0, 'mangle_prompt', (1, 'oversize')),
        (0, 'mangle_prompt', (4, 'garbage'))])
    out = SV.serve_continuous(ARCH, attn_impl='einsum', faults=inj, **KW)
    assert out['rejected'] == 2
    assert out['terminal'][1] == 'reject' and out['terminal'][4] == 'reject'
    assert out['completed'] == KW['n_requests'] - 2
    _assert_parity(out, [0, 2, 3, 5], KW['prompt_len'], KW['gen_len'])


def test_livelock_regression_tight_pool_fails_terminally():
    """End-to-end livelock regression at a minimal pool: a permanent
    squeeze leaves room for no lane; the retry budget fails the requests
    terminally and the serve returns instead of stalling forever."""
    inj = faults.FaultInjector(
        seed=0,
        profile=faults.FaultProfile(pool_squeeze=1.0, squeeze_pages=64,
                                    squeeze_steps=2))
    out = SV.serve_continuous(ARCH, attn_impl='einsum', slots=2,
                              n_requests=3, prompt_len=16, gen_len=8,
                              page_size=4, retry_budget=2, deadline=40,
                              quiet=True, faults=inj)
    assert out['completed'] == 0
    assert out['failed'] == 3
    assert set(out['terminal'].values()) == {'fail'}


# ----------------------------------------------------------------------------
# the seeded soak
# ----------------------------------------------------------------------------
def test_chaos_soak_seeded_profile():
    """N decode steps under a random (seeded) fault schedule with every
    lossless fault kind live: allocator invariants audited after every
    step, every submitted request reaches exactly one terminal state, and
    every request that finished decodes token-identically vs solo (no
    fault in this profile may alter a surviving stream's tokens)."""
    prof = faults.FaultProfile(pool_squeeze=0.06, squeeze_pages=3,
                               squeeze_steps=3, preempt_storm=0.05,
                               poison_page=0.04, poison_logits=0.04,
                               cancel=0.03)
    inj = faults.FaultInjector(seed=11, profile=prof)
    audited = [0]
    out = SV.serve_continuous(ARCH, attn_impl='einsum', n_requests=8,
                              slots=3, prompt_len=16, gen_len=8,
                              page_size=4, retry_budget=16, quiet=True,
                              faults=inj,
                              step_hook=_invariant_hook(audited))
    assert audited[0] == out['steps'] > 0
    assert not inj.touched                   # no drop_quant in the profile
    # terminal accounting covers the whole stream (serve_continuous
    # already raises if not — pin the partition here too)
    assert sorted(out['terminal']) == list(range(8))
    n_term = (out['completed'] + out['failed'] + out['rejected']
              + out['cancelled'])
    assert n_term == 8
    # the soak must actually have injected something
    assert sum(inj.counts.values()) > 0
    # every finished request is token-identical with solo decode
    _assert_parity(out, sorted(out['outputs']), 16, 8)


def test_chaos_soak_kv_quant_tier():
    """The same soak over the int8-tier stream (drop-quant live too):
    robustness gates only — the int8 cold tier is lossy by design, so the
    gate is terminal accounting + invariants + no crash, not token
    parity against the fp oracle."""
    prof = faults.FaultProfile(pool_squeeze=0.05, squeeze_pages=2,
                               squeeze_steps=3, preempt_storm=0.05,
                               poison_page=0.04, poison_logits=0.04,
                               drop_quant=0.05, cancel=0.03)
    inj = faults.FaultInjector(seed=5, profile=prof)
    audited = [0]
    out = SV.serve_continuous(ARCH, attn_impl='flash', kv_quant=True,
                              hot_window=1, n_requests=8, slots=3,
                              prompt_len=16, gen_len=8, page_size=4,
                              retry_budget=16, quiet=True, faults=inj,
                              step_hook=_invariant_hook(audited))
    assert audited[0] == out['steps'] > 0
    assert sorted(out['terminal']) == list(range(8))
    n_term = (out['completed'] + out['failed'] + out['rejected']
              + out['cancelled'])
    assert n_term == 8
    assert out['completed'] >= 4             # the stream survives the storm


def test_poisoned_shared_page_quarantines_every_owner_losslessly():
    """Chaos x prefix sharing: a NaN'd SHARED page trips the integrity
    sentinel in EVERY owner lane, the first quarantine retires the page
    from the prefix table (no later admission can acquire the suspect
    content), the deferred scrub never zeroes it while other owners still
    read it — and the retried requests land token-identical to solo."""
    rs = np.random.RandomState(0)
    vocab = configs.get(ARCH, smoke=True).vocab_size
    sysp = rs.randint(1, vocab, size=12).astype(np.int32)   # 3 full pages
    reqs = [SV.Request(rid=i,
                       prompt=np.concatenate(
                           [sysp, rs.randint(1, vocab, size=1 + i)
                            .astype(np.int32)]),
                       target_gen=6) for i in range(4)]
    inj = faults.FaultInjector(seed=0, schedule=[(2, 'poison_page', None)])
    audited = [0]
    out = SV.serve_continuous(ARCH, attn_impl='einsum', slots=4,
                              prompt_len=16, gen_len=8, page_size=4,
                              prefix_cache=True, request_stream=reqs,
                              faults=inj, quiet=True,
                              step_hook=_invariant_hook(audited))
    assert audited[0] == out['steps']
    assert out['completed'] == len(reqs)
    pois = [e for e in out['event_log']
            if e['kind'] == 'fault' and e.get('fault') == 'poison_page']
    assert len(pois) == 1 and len(pois[0]['owners']) >= 2   # shared hit
    assert out['quarantined'] >= len(pois[0]['owners'])
    for req in reqs:   # lossless recovery for every owner
        want = _reference_tokens(req, 16, 8)
        assert out['outputs'][req.rid] == want, (req.rid,)


def test_chaos_soak_with_prefix_sharing():
    """The PR 7 seeded soak with the prefix cache on: a shared-prefix
    stream under the full chaos profile (squeezes, storms, poisons,
    cancels) keeps ``check_invariants`` — now auditing refcounts, the
    prefix-table bijection, and the evictable LRU — green after every
    step, completes the stream, and every request the injector did not
    touch decodes token-identically to solo."""
    rs = np.random.RandomState(5)
    vocab = configs.get(ARCH, smoke=True).vocab_size
    sysp = rs.randint(1, vocab, size=8).astype(np.int32)
    reqs = [SV.Request(rid=i,
                       prompt=np.concatenate(
                           [sysp, rs.randint(1, vocab, size=1 + (i % 5))
                            .astype(np.int32)]),
                       target_gen=5 + (i % 3)) for i in range(8)]
    prof = faults.FaultProfile(pool_squeeze=0.06, squeeze_pages=3,
                               squeeze_steps=3, preempt_storm=0.05,
                               poison_page=0.04, poison_logits=0.04,
                               cancel=0.03)
    inj = faults.FaultInjector(seed=11, profile=prof)
    audited = [0]
    out = SV.serve_continuous(ARCH, attn_impl='einsum', slots=3,
                              prompt_len=16, gen_len=8, page_size=4,
                              prefix_cache=True, request_stream=reqs,
                              retry_budget=16, quiet=True, faults=inj,
                              step_hook=_invariant_hook(audited))
    assert audited[0] == out['steps'] > 0
    assert out['prefix']['hits'] > 0         # the stream actually shared
    assert sorted(out['terminal']) == list(range(8))
    cancelled = {e['rid'] for e in out['event_log'] if e['kind'] == 'cancel'}
    for req in reqs:
        if req.rid in inj.touched or req.rid in cancelled:
            continue
        if req.rid not in out['outputs']:
            continue
        if len(out['outputs'][req.rid]) < req.target_gen:
            continue                          # failed/deadline-cut lanes
        want = _reference_tokens(req, 16, 8)
        assert out['outputs'][req.rid] == want, (req.rid,)
