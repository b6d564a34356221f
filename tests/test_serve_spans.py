"""The serving loop's phase spans (``runtime.telemetry.Span``): what a
profiler trace of ``serve_continuous`` holds, the per-phase counter and
slowest steps they feed, the Chrome trace beside them, and the
benchmark's readers of them (``chipbench/harness/spans.py``) on a CPU
trace and on a short trace recorded on the chip."""

import json
import pathlib
import types

import jax
import pytest

from chipbench.harness import cell as cell_mod
from chipbench.harness import spans as spans_mod
from chipbench.harness import trace as trace_mod
from repro.launch import serve
from repro.runtime import telemetry as T

ARCH = 'stablelm-1.6b'
SMOKE = dict(slots=3, n_requests=6, prompt_len=16, gen_len=8, page_size=4)
LOOP_PHASES = {'step', 'admit', 'prefill', 'quantize', 'telemetry',
               'dispatch', 'wait', 'absorb', 'hook'}
DATA = pathlib.Path(__file__).resolve().parents[1] / 'chipbench' / \
    'testdata'


def _profiled(outdir, **kw):
    """One smoke serve under the profiler (host spans only, as the
    benchmark's tracer records them)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(outdir), profiler_options=opts)
    try:
        out = serve.serve_continuous(ARCH, attn_impl='einsum', quiet=True,
                                     kv_quant=True, hot_window=1,
                                     step_hook=lambda *a: None,
                                     **SMOKE, **kw)
    finally:
        jax.profiler.stop_trace()
    return out


def _ctx(outdir):
    return dict(tracer=types.SimpleNamespace(outdir=pathlib.Path(outdir),
                                             t0=0.0))


@pytest.fixture(scope='module')
def traced(tmp_path_factory):
    d = tmp_path_factory.mktemp('serve-trace')
    chrome = d / 'serve.trace.json'
    out = _profiled(d / 'profile', trace=str(chrome))
    return out, spans_mod.load(_ctx(d / 'profile')), chrome


def _events(out, kind):
    return [e for e in out['event_log'] if e['kind'] == kind]


def test_trace_holds_a_span_per_step_and_admission(traced):
    out, roots, _ = traced
    steps = spans_mod.named(roots, 'serve.step')
    assert len(steps) == out['steps']
    assert [s.start_ns for s in steps] == sorted(s.start_ns for s in steps)
    assert len(spans_mod.named(roots, 'serve.admit')) == \
        len(_events(out, 'admit'))
    # every admission nests its prefill and its first-token wait
    for adm in spans_mod.named(roots, 'serve.admit'):
        assert [c.name for c in adm.children] == ['serve.prefill',
                                                  'serve.wait']
    # every decoding step dispatches, then waits for the device
    for st in steps:
        names = [c.name for c in st.children]
        assert 'serve.dispatch' in names and 'serve.wait' in names
        assert names.index('serve.dispatch') < names.index('serve.wait')
        assert names[-1] == 'serve.hook'


def test_program_names_the_readers_match(traced):
    """``prefill.dev_ms`` and ``decode.dev_ms.*`` find the step programs
    by their jitted names; each runs inside its phase."""
    _, roots, _ = traced
    prog = {'serve.prefill': 'prefill_step', 'serve.dispatch': 'decode_step'}
    readers = {n: cell_mod.reader_path(n) for n in
               ('prefill.dev_ms', 'decode.dev_ms.bf16')}
    assert "PROGRAM = r'prefill_step'" in readers['prefill.dev_ms']\
        .read_text()
    assert "PROGRAM = r'decode_step'" in readers['decode.dev_ms.bf16']\
        .read_text()
    pd = trace_mod.load(traced[2].parent / 'profile')
    host = [ev for pl in pd.planes if pl.name.startswith('/host:')
            for ln in pl.lines for ev in ln.events]
    for phase, fn in prog.items():
        runs = [ev for ev in host if ev.name == f'PjitFunction({fn})']
        assert runs
        inside = spans_mod.named(roots, phase)
        for ev in runs:
            assert any(s.start_ns <= ev.start_ns and ev.end_ns <= s.end_ns
                       for s in inside), (fn, phase)


def test_phase_counter_and_slowest_steps(traced):
    out, _, _ = traced
    snap = out['telemetry']
    phases = snap['metrics']['serve_phase_seconds_total']['values']
    assert set(phases) == LOOP_PHASES
    assert all(v >= 0 for v in phases.values())
    # a nested phase counts in its parent too
    assert phases['step'] >= phases['admit'] >= phases['prefill']
    slow = snap['slowest_steps']
    assert 0 < len(slow) <= T.SLOWEST_STEPS == 8
    assert len(slow) == min(8, out['steps'])
    secs = [s['seconds'] for s in slow]
    assert secs == sorted(secs, reverse=True)
    assert len({s['step'] for s in slow}) == len(slow)
    for s in slow:
        assert set(s['phases']) <= LOOP_PHASES
        assert all(v >= -1e-9 for v in s['phases'].values())
        assert sum(s['phases'].values()) <= s['seconds'] + 1e-9
        assert sum(s['phases'].values()) == pytest.approx(s['seconds'],
                                                           rel=1e-6)
    # the step histogram is fed by the same spans
    assert snap['metrics']['serve_step_seconds']['count'] == out['steps']
    assert snap['metrics']['serve_step_seconds']['max'] == \
        pytest.approx(secs[0])


def test_chrome_trace_has_the_profiler_span_names(traced):
    _, roots, chrome = traced
    tr = json.loads(chrome.read_text())
    x = [e for e in tr['traceEvents'] if e['ph'] == 'X']
    assert {e['name'] for e in x} == \
        {s.name for r in roots for s in r.walk()}
    # admissions and what nests in them sit on their lane's track
    lanes = {e['tid'] for e in x if e['name'] in ('serve.admit',
                                                  'serve.prefill')}
    assert lanes and 0 not in lanes
    assert all(e['tid'] == 0 for e in x if e['name'] == 'serve.step')


def test_readers_read_the_cpu_trace(traced):
    out, roots, chrome = traced
    ctx = _ctx(chrome.parent / 'profile')
    host = cell_mod.read_metric('serve.host_ms.chat', ctx)
    admit = cell_mod.read_metric('admit.ms.chat', ctx)
    assert host is not None and admit is not None
    steps = spans_mod.named(roots, 'serve.step')
    mean_step_ms = 1e3 * sum(s.seconds for s in steps) / len(steps)
    assert 0 < host < mean_step_ms
    assert admit > 0
    assert cell_mod.read_metric('serve.host_ms.w8a8', ctx) == host
    assert cell_mod.read_metric('serve.host_ms.bf16', ctx) == host


def test_no_metrics_keeps_the_profiler_spans(tmp_path):
    """``metrics=False`` drops the counters and the snapshot, not the
    annotations."""
    out = _profiled(tmp_path, metrics=False)
    assert 'telemetry' not in out
    roots = spans_mod.load(_ctx(tmp_path))
    assert len(spans_mod.named(roots, 'serve.step')) == out['steps']
    assert len(spans_mod.named(roots, 'serve.admit')) == \
        len(_events(out, 'admit'))
    assert spans_mod.host_ms(roots) > 0


def test_readers_find_nothing_without_spans():
    """A run with no trace, and a trace of a program without the spans
    (the earlier recorded chip trace), read ``None``."""
    assert cell_mod.read_metric('serve.host_ms.chat', dict(tracer=None)) \
        is None
    ctx = _ctx(DATA / 'trace-12b-offline')
    assert spans_mod.load(ctx) == []
    assert cell_mod.read_metric('serve.host_ms.w8a8', ctx) is None
    assert cell_mod.read_metric('admit.ms.chat', ctx) is None


def test_innermost_span_takes_the_gap():
    S = spans_mod.Span
    step = S('serve.step', 0, 100, [S('serve.dispatch', 10, 40,
                                      [S('serve.telemetry', 12, 20)]),
                                    S('serve.wait', 40, 90)])
    nxt = S('serve.step', 110, 200, [S('serve.dispatch', 111, 150)])
    roots = [step, nxt]
    assert spans_mod.innermost(roots, 13, 19) == 'serve.telemetry'
    assert spans_mod.innermost(roots, 10, 40) == 'serve.dispatch'
    # the phase with the most of the gap, even short of half of it
    assert spans_mod.innermost(roots, 30, 50) == 'serve.dispatch'
    # the step's own time holds more than any phase in it
    assert spans_mod.innermost(roots, 88, 100) == 'serve.step'
    # across two steps: the one holding more, then its phase
    assert spans_mod.innermost(roots, 95, 130) == 'serve.dispatch'
    assert spans_mod.innermost(roots, 99, 112) == spans_mod.OUTSIDE
    assert spans_mod.innermost(roots, 200, 300) == spans_mod.OUTSIDE
    by = spans_mod.idle_by_phase(roots, [(13, 19), (50, 60), (95, 130),
                                         (200, 300)])
    assert by == pytest.approx({spans_mod.OUTSIDE: 100e-9,
                                'serve.dispatch': 35e-9,
                                'serve.wait': 10e-9,
                                'serve.telemetry': 6e-9})
    split = spans_mod.idle_split(roots, [(13, 19), (30, 50), (95, 130)])
    assert split == pytest.approx({'serve.dispatch': 29e-9,
                                   spans_mod.OUTSIDE: 10e-9,
                                   'serve.wait': 10e-9,
                                   'serve.step': 6e-9,
                                   'serve.telemetry': 6e-9})


@pytest.fixture(scope='module')
def chat_trace():
    """A 0.44-s trace of ``stablelm-1.6b.chat`` on the chip around one
    admission (``facts.json`` holds what the harness saw and read)."""
    d = DATA / 'trace-1.6b-chat'
    facts = json.loads((d / 'facts.json').read_text())
    pd = trace_mod.load(d / 'trace.xplane.pb')
    return d, facts, pd, trace_mod.reduce(pd), spans_mod.collect(pd)


def test_recorded_trace_programs_and_spans(chat_trace):
    _, facts, _, summ, roots = chat_trace
    t = facts['traced']
    assert len(summ['programs']['jit_prefill_step']) == len(t['prefills'])
    assert len(summ['programs']['jit_decode_step']) == t['decode_steps']
    assert len(spans_mod.named(roots, 'serve.admit')) == \
        len(t['prefills']) == facts['spans']['admits'] == 1
    assert len(spans_mod.named(roots, 'serve.step')) == \
        facts['spans']['whole_steps'] == 2
    assert sorted({s.name for r in roots for s in r.walk()}) == \
        facts['spans']['names']


def test_recorded_trace_host_spans_share_the_device_clock(chat_trace):
    """Each device run of a step program lies inside the host phase that
    dispatched it and waited for it."""
    _, _, pd, _, roots = chat_trace
    dev = [p for p in pd.planes if p.name.startswith('/device:TPU')][0]
    mods = [e for ln in dev.lines if ln.name == 'XLA Modules'
            for e in ln.events]
    prefill = [e for e in mods if 'prefill_step' in e.name]
    (admit,) = spans_mod.named(roots, 'serve.admit')
    assert all(admit.start_ns < e.start_ns and e.end_ns < admit.end_ns
               for e in prefill)
    checked = 0
    for s in spans_mod.named(roots, 'serve.step'):
        (disp,) = [c for c in s.children if c.name == 'serve.dispatch']
        (wait,) = [c for c in s.children if c.name == 'serve.wait']
        runs = [e for e in mods if 'decode_step' in e.name
                and s.start_ns < e.start_ns < s.end_ns]
        assert len(runs) == 1
        assert disp.start_ns < runs[0].start_ns < disp.end_ns
        assert wait.start_ns < runs[0].end_ns < wait.end_ns
        checked += 1
    assert checked == 2


def test_recorded_trace_readers(chat_trace):
    d, facts, _, summ, roots = chat_trace
    ctx = _ctx(d)
    read = facts['read']
    for name in ('serve.host_ms.chat', 'admit.ms.chat'):
        assert cell_mod.read_metric(name, ctx) == pytest.approx(read[name])
    # the first-token wait follows the prefill and the tail-zero op on
    # the device's one stream, so an admission outlasts both
    tail = summ['programs']['jit_zero_tree_tail']
    assert 1e3 * (sum(summ['programs']['jit_prefill_step']) + sum(tail)) \
        < read['admit.ms.chat'] < read['prefill.dev_ms'] + 1e3 * sum(tail) + 10
    assert 0 < read['serve.host_ms.chat'] < 10


@pytest.mark.parametrize('attribute', ['idle_by_phase', 'idle_split'])
def test_recorded_trace_idle_lies_in_named_phases(chat_trace, attribute):
    _, facts, _, summ, roots = chat_trace
    by = getattr(spans_mod, attribute)(roots, summ['gaps'])
    idle = sum(e - s for s, e in summ['gaps']) * 1e-9
    assert sum(by.values()) == pytest.approx(idle)
    assert by == pytest.approx(facts['reduced'][attribute + '_s'])
    unnamed = by.get('serve.step', 0.0) + by.get(spans_mod.OUTSIDE, 0.0)
    assert unnamed <= 0.1 * idle
