"""Continuous batching end-to-end: a stream of heterogeneous-length
requests admitted / decoded / evicted / re-admitted over the paged KV
cache, under one jit'd decode step — plus the temperature/top-k sampling
path in the serve steps."""

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
from repro.runtime import serve_step as SS

ARCH = 'stablelm-1.6b'
# the MLA member of the grid: continuous batching over the paged LATENT
# pool (deepseek-v3 smoke = MLA + MoE + dense prefix)
MLA_ARCH = 'deepseek-v3-671b'


@functools.lru_cache(maxsize=2)
def _reference_model(arch=ARCH):
    """Shared across reference decodes: params + jitted steps are identical
    for every request (same cfg, same shapes)."""
    cfg = configs.get(arch, smoke=True)
    yoco, rt = YocoConfig(mode='bf16'), ModelRuntime()
    params = model_mod.init_params(jax.random.key(0), cfg)
    prefill = jax.jit(SS.make_prefill_step(cfg, yoco, rt))
    decode = jax.jit(SS.make_decode_step(cfg, yoco, rt))
    return cfg, params, prefill, decode


def _reference_tokens(req, prompt_len, gen_len, arch=ARCH):
    """Greedy-decode one request alone through the contiguous einsum path:
    the oracle the continuous scheduler must reproduce token-for-token."""
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


def _solo_vs_continuous(arch, *, n=5, prompt_len=16, gen_len=8):
    """Token-for-token solo-vs-continuous parity over a contended stream
    (slots < requests forces eviction + re-admission waves)."""
    out = SV.serve_continuous(arch, slots=2, n_requests=n,
                              prompt_len=prompt_len, gen_len=gen_len,
                              page_size=4, attn_impl='einsum', quiet=True)
    assert out['completed'] == n
    assert out['steps'] > gen_len          # slots < requests => multiple waves
    if out['decode_compilations'] is not None:
        assert out['decode_compilations'] == 1   # no retrace across churn
    cfg = configs.get(arch, smoke=True)
    dc = synthetic.for_arch(cfg, global_batch=n, seq_len=prompt_len)
    prompts = np.asarray(synthetic.make_batch(dc, 0)['inputs'])
    for req in SV._ragged_stream(n, prompt_len, gen_len, prompts):
        want = _reference_tokens(req, prompt_len, gen_len, arch)
        assert out['outputs'][req.rid] == want, (req.rid,
                                                 out['outputs'][req.rid],
                                                 want)


def test_continuous_serve_matches_single_request_reference():
    """5 ragged requests over 2 slots (forced re-admission) with a pool
    tight enough to queue: every emitted token must equal the request's
    solo contiguous-decode tokens."""
    _solo_vs_continuous(ARCH)


@pytest.mark.slow
def test_continuous_serve_matches_single_request_reference_mla():
    """The same token-for-token contract on the MLA family: deepseek-v3
    smoke over the paged latent pool (one cl pool per layer, same block
    tables) must reproduce each request's solo contiguous absorbed
    decode exactly."""
    _solo_vs_continuous(MLA_ARCH, n=4, gen_len=6)


def _preemption_is_lossless(arch, tight_pages):
    kwargs = dict(slots=3, n_requests=5, prompt_len=16, gen_len=8,
                  page_size=4, attn_impl='einsum', quiet=True)
    tight = SV.serve_continuous(arch, num_pages=tight_pages, **kwargs)
    roomy = SV.serve_continuous(arch, num_pages=None, **kwargs)
    assert tight['preempted'] > 0
    assert tight['outputs'] == roomy['outputs']
    assert tight['completed'] == roomy['completed'] == 5
    return tight


def test_continuous_serve_preemption_is_lossless():
    """A pool too small for all lanes preempts-and-requeues; the final
    token streams must be identical to an uncontended run."""
    _preemption_is_lossless(ARCH, 9)


@pytest.mark.slow
def test_continuous_serve_preemption_is_lossless_mla():
    """Forced preemption + recompute re-admission on the paged LATENT
    pool: deepseek token streams must survive the churn unchanged."""
    _preemption_is_lossless(MLA_ARCH, 9)


@pytest.mark.slow
def test_continuous_serve_flash_matches_einsum():
    """The scalar-prefetch paged kernel serves the same stream with the
    same tokens as the densified einsum oracle."""
    kwargs = dict(slots=2, n_requests=3, prompt_len=16, gen_len=6,
                  page_size=4, quiet=True)
    a = SV.serve_continuous(ARCH, attn_impl='einsum', **kwargs)
    b = SV.serve_continuous(ARCH, attn_impl='flash', **kwargs)
    assert a['outputs'] == b['outputs']


@pytest.mark.slow
def test_continuous_serve_flash_matches_einsum_mla():
    """flash_decode_paged_mla serves the same deepseek stream with the
    same tokens as the densified absorbed-einsum oracle."""
    kwargs = dict(slots=2, n_requests=3, prompt_len=16, gen_len=6,
                  page_size=4, quiet=True)
    a = SV.serve_continuous(MLA_ARCH, attn_impl='einsum', **kwargs)
    b = SV.serve_continuous(MLA_ARCH, attn_impl='flash', **kwargs)
    assert a['outputs'] == b['outputs']


# ----------------------------------------------------------------------------
# serving-mode routing table (pinned: which families reach which modes)
# ----------------------------------------------------------------------------
def test_continuous_serve_routing_table():
    """--continuous admits every token-input family — GQA, MLA (fp or
    int8-tiered), SSM, and hybrid — and rejects exactly the non-token
    frontends, each with its own message. The SSM/hybrid block fell with
    the RecurrentLayout slot ops; only the stub frontend's inability to
    requeue non-token prompts remains."""
    # blocked: non-token inputs can't requeue through the stub frontend
    for arch in ('musicgen-large', 'qwen2-vl-72b'):
        with pytest.raises(ValueError, match='token streams'):
            SV.serve_continuous(arch, quiet=True)
    # blocked: pure-SSM recurrent state has no int8 KV tier to quantize
    with pytest.raises(ValueError, match='recurrent state'):
        SV.serve_continuous('mamba2-780m', kv_quant=True, quiet=True)
    # admitted: every token family constructs + drains an empty stream
    # (GQA/MLA fp and int8-tiered, SSM, and hybrid alike — the gate must
    # not regress to a blanket SSM/hybrid block)
    for arch, kv_quant in ((ARCH, False), (ARCH, True), (MLA_ARCH, False),
                           (MLA_ARCH, True), ('mamba2-780m', False),
                           ('zamba2-1.2b', False), ('zamba2-1.2b', True)):
        out = SV.serve_continuous(arch, n_requests=0, prompt_len=8,
                                  gen_len=4, page_size=4,
                                  kv_quant=kv_quant, quiet=True)
        assert out['completed'] == 0


# ----------------------------------------------------------------------------
# sampling (the make_decode_step greedy/non-greedy satellite)
# ----------------------------------------------------------------------------
def test_sample_tokens_top_k_support():
    key = jax.random.key(0)
    logits = jnp.asarray(np.random.RandomState(0).randn(64, 100) * 3)
    top2 = set(np.asarray(jax.lax.top_k(logits, 2)[1]).ravel().tolist())
    toks = SS.sample_tokens(logits, key, temperature=1.0, top_k=2)
    assert set(np.asarray(toks).tolist()) <= top2
    # temperature <= 0 is the greedy limit
    greedy = SS.sample_tokens(logits, key, temperature=0.0)
    np.testing.assert_array_equal(np.asarray(greedy),
                                  np.asarray(jnp.argmax(logits, -1)))


def test_sample_tokens_temperature_sharpens():
    """Low temperature concentrates mass on the argmax."""
    key = jax.random.key(1)
    logits = jnp.asarray(np.random.RandomState(1).randn(256, 32))
    cold = SS.sample_tokens(logits, key, temperature=0.01)
    hot = SS.sample_tokens(logits, key, temperature=5.0)
    am = np.asarray(jnp.argmax(logits, -1))
    agree_cold = float(np.mean(np.asarray(cold) == am))
    agree_hot = float(np.mean(np.asarray(hot) == am))
    assert agree_cold > 0.95, agree_cold
    assert agree_hot < agree_cold


def test_decode_step_sampled_signature_and_determinism():
    """Non-greedy decode steps take a PRNG key and are reproducible under
    the same key; different keys may differ."""
    cfg = configs.get(ARCH, smoke=True)
    yoco, rt = YocoConfig(mode='bf16'), ModelRuntime()
    params = model_mod.init_params(jax.random.key(0), cfg)
    step = SS.make_decode_step(cfg, yoco, rt, greedy=False, temperature=1.0,
                               top_k=8)
    cache = model_mod.init_cache_tree(cfg, 2, 8)
    tok = jnp.array([1, 2], jnp.int32)
    key = jax.random.key(3)
    t1, logits, _ = step(params, tok, jnp.int32(0), cache, key)
    t2, _, _ = step(params, tok, jnp.int32(0), cache, key)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    # sampled ids stay inside the top-k set of the step's own logits
    topk_ids = np.asarray(jax.lax.top_k(logits, 8)[1])
    for b in range(2):
        assert int(t1[b]) in topk_ids[b].tolist()


@pytest.mark.parametrize('mode,prequantize,kv_quant', [
    ('bf16', False, False), ('bf16', False, True), ('w8a8', True, False)])
def test_oracle_check_reports_small_logit_error(mode, prequantize, kv_quant):
    """``oracle_check`` runs the first decode step on the einsum oracle as
    well: the flash step's logits agree within the bounds chip_smoke.py
    holds the chip to, and the oracle adds no decode compilation."""
    out = SV.serve_continuous(ARCH, slots=2, n_requests=3, prompt_len=16,
                              gen_len=4, page_size=4, mode=mode,
                              prequantize=prequantize, kv_quant=kv_quant,
                              hot_window=1, attn_impl='flash',
                              oracle_check=True, metrics=False, quiet=True)
    assert out['completed'] == 3
    assert out['decode_compilations'] == 1
    assert 0.0 <= out['oracle_logit_err'] <= (0.15 if mode == 'w8a8'
                                              else 0.05)
