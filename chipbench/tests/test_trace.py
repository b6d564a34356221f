"""The trace reduction, checked on a short trace recorded on the chip
(TPU v5 lite, ``stablelm-12b-w8a8.offline-long``, the last 0.4 s of a
window; ``testdata/trace-12b-offline/facts.json`` holds what the load
generator saw in that window and the configuration's depth)."""

import json
import pathlib

import numpy as np
import pytest

DATA = pathlib.Path(__file__).resolve().parents[1] / 'testdata' / \
    'trace-12b-offline'


@pytest.fixture(scope='module')
def recorded():
    from chipbench.harness import trace as tm
    facts = json.loads((DATA / 'facts.json').read_text())
    pd = tm.load(DATA / 'trace.xplane.pb')
    return tm, pd, tm.reduce(pd), facts


def test_one_program_run_per_step(recorded):
    tm, _, s, facts = recorded
    t = facts['traced']
    assert s['n_devices'] == 1
    assert len(s['programs']['jit_decode_step']) == t['decode_steps']
    assert len(s['programs'].get('jit_prefill_step', [])) == t['prefills']


def test_kernel_calls_follow_the_model(recorded):
    """Every decode step runs the q8 flash kernel once per layer and the
    int8 matmul once per projection and layer plus the LM head; every
    prefill runs the matmuls (its attention is not the kernel)."""
    tm, _, s, facts = recorded
    t, L = facts['traced'], facts['layers']
    n_flash, t_flash = tm.kernel_seconds(s, r'^flash_decode_gqa_paged_q8$')
    n_mm, t_mm = tm.kernel_seconds(s, r'^int8_matmul$')
    assert n_flash == L * t['decode_steps']
    assert n_mm == (7 * L + 1) * (t['decode_steps'] + t['prefills'])
    assert 0 < t_flash + t_mm < s['busy_s']


def test_busy_is_the_union_of_program_intervals(recorded):
    """Busy time against a brute-force union on a 1 us grid."""
    tm, pd, s, _ = recorded
    dev = [p for p in pd.planes if p.name.startswith('/device:TPU')][0]
    mods = [ln for ln in dev.lines if ln.name == 'XLA Modules'][0]
    iv = [(e.start_ns, e.end_ns) for e in mods.events]
    lo = min(a for a, _ in iv)
    grid = np.zeros(int((max(b for _, b in iv) - lo) / 1000) + 2, bool)
    for a, b in iv:
        grid[int((a - lo) / 1000):int((b - lo) / 1000)] = True
    assert s['busy_s'] == pytest.approx(grid.sum() * 1e-6, rel=1e-3, abs=5e-5)
    assert 0 < s['busy_s'] <= s['window_s']


def test_gaps_and_ops_are_labelled(recorded):
    tm, _, s, _ = recorded
    gaps = tm.label_gaps(s)
    assert gaps and all(g[1] > 0 for g in gaps)
    assert sum(g for _, g in gaps) <= s['window_s'] - s['busy_s'] + 1e-9
    names = [n for n, _ in tm.top_ops(s)]
    assert 'flash_decode_gqa_paged_q8' in names
    assert not any(n.startswith('while') for n in names)
