"""A run with the timed path broken underneath reads ``correct`` false.

Each case drives the whole harness on the CPU at smoke widths (the look
for a chip skipped) with the program's decode step broken in one way a
served cell can break: a step that hands back its KV state unchanged, a
token altered where the step produces it, the odd half of the batch's
lanes left out. (A one-chip cell has no exchange between chips to leave out.)
"""

import time

import pytest

from conftest import smoke

CELLS = ['stablelm-1.6b.chat', 'stablelm-12b-w8a8.offline-long']


def _broken_serve(monkeypatch, fault):
    import jax.numpy as jnp
    from repro.launch import serve as serve_mod
    from repro.runtime import serve_step as SS
    make = SS.make_decode_step

    def make_broken(*a, **kw):
        step = make(*a, **kw)

        def broken(params, tok, pos, cache, *rest):
            nt, logits, new_cache = step(params, tok, pos, cache, *rest)
            if fault == 'state_unchanged':
                new_cache = cache
            elif fault == 'token_altered':
                nt = (nt + 1) % logits.shape[-1]
            elif fault == 'half_batch':
                # the odd lanes are left out: they hand back their input
                # token instead of the one computed
                lane = jnp.arange(nt.shape[0]).reshape(
                    (-1,) + (1,) * (nt.ndim - 1))
                nt = jnp.where(lane % 2 == 1, jnp.reshape(tok, nt.shape), nt)
            return nt, logits, new_cache
        return broken

    monkeypatch.setattr(SS, 'make_decode_step', make_broken)
    return serve_mod.serve_continuous


@pytest.mark.parametrize('fault', ['state_unchanged', 'token_altered',
                                   'half_batch'])
@pytest.mark.parametrize('name', CELLS)
def test_fault_reads_incorrect(monkeypatch, load_cell, name, fault):
    from chipbench.harness import cell as C
    serve = _broken_serve(monkeypatch, fault)
    r = C.run_cell(name, 2 ** 33 + 5, 3.0, False, t_start=time.perf_counter(),
                   cell=smoke(load_cell(name)), require_chip=False,
                   serve_fn=serve)
    assert r['correct'] is False
    gap = r['compared']['max_logit_gap']
    assert gap['value'] > gap['limit']


@pytest.mark.parametrize('name', CELLS)
def test_sound_run_reads_correct(load_cell, name):
    from chipbench.harness import cell as C
    r = C.run_cell(name, 2 ** 33 + 5, 3.0, False, t_start=time.perf_counter(),
                   cell=smoke(load_cell(name)), require_chip=False)
    assert r['correct'] is True, r['compared']
    assert r['attempted'] > 0 and r['failed'] == 0
    assert list(r)[-1] == 'compared'


def test_sample_covers_half_the_lanes_with_served_prefixes():
    """One request to a lane, the longest first, half of the lanes (at
    least four) covered; a request cut at the close counts with the
    tokens it was served."""
    from chipbench.harness import cell as C
    from chipbench.harness.loadgen import Rec
    recs = [Rec(rid=i, plen=4, due=0.0, tokens=[1] * (5 + i % 7),
                slot=i % 12) for i in range(36)]
    for seed in (1, 2 ** 40 + 3):
        s = C.sample_requests(recs, seed, slots=12)
        assert len(s) == 6
        assert len({r.slot for r in s}) == 6
        assert len(s[0].tokens) == 11
    assert len(C.sample_requests(recs[:5], 7, slots=4)) == 4
    assert C.sample_requests([], 7, slots=4) == []


def test_dotted_names_share_a_reader():
    from chipbench.harness import cell as C
    assert C.reader_path('device.idle.chat') == C.reader_path('device.idle.w8a8')
    assert C.reader_path('output_tok_s.bf16').name == 'output_tok_s.py'
    with pytest.raises(FileNotFoundError):
        C.reader_path('no_such_metric.w8a8')
