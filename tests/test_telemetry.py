"""Serving-telemetry tests (PR 8): histogram math against numpy, lifecycle
spans from scripted event sequences, the EventLog timestamp audit, the
EnergyMeter priced EXACTLY like direct hwmodel calls, metrics-vs-EventLog
cross-checks on real (clean and seeded-chaos) continuous serves, and the
Chrome-trace schema. ``make test-telemetry`` runs this file."""

import json

import numpy as np
import pytest

from repro import configs
from repro.core import hwmodel
from repro.launch import serve
from repro.runtime import faults
from repro.runtime import telemetry as T

pytestmark = pytest.mark.telemetry

ARCH = 'stablelm-1.6b'
SMOKE = dict(slots=3, n_requests=6, prompt_len=16, gen_len=8, page_size=4)


# ----------------------------------------------------------------------------
# metric primitives
# ----------------------------------------------------------------------------
def test_histogram_percentiles_within_bucket_width_of_numpy():
    rng = np.random.default_rng(0)
    vals = rng.lognormal(mean=-6.0, sigma=1.5, size=4000)   # ~ms latencies
    h = T.Histogram('h')
    for v in vals:
        h.observe(float(v))
    bounds = list(h.bounds)
    for q in (0.50, 0.90, 0.99):
        est = h.percentile(q)
        ref = float(np.quantile(vals, q))
        # the estimator is exact to one bucket width at the landing bucket
        i = np.searchsorted(bounds, ref)
        lo = bounds[i - 1] if i > 0 else 0.0
        hi = bounds[i] if i < len(bounds) else float(vals.max())
        assert abs(est - ref) <= (hi - lo) + 1e-12, (q, est, ref)
        assert vals.min() <= est <= vals.max()


def test_histogram_empty_and_single_value():
    h = T.Histogram('h', buckets=(1.0, 2.0))
    assert h.percentile(0.5) is None
    h.observe(1.5)
    # clamped to the observed range: one sample pins every percentile
    for q in (0.0, 0.5, 0.99):
        assert h.percentile(q) == 1.5
    snap = h.snapshot()
    assert snap['count'] == 1 and snap['min'] == snap['max'] == 1.5


def test_histogram_prometheus_render_is_cumulative():
    h = T.Histogram('lat', buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 0.5, 5.0):
        h.observe(v)
    lines = h.render()
    assert 'lat_bucket{le="0.1"} 1' in lines
    assert 'lat_bucket{le="1.0"} 3' in lines
    assert 'lat_bucket{le="+Inf"} 4' in lines       # == _count, always
    assert 'lat_count 4' in lines


def test_counter_gauge_label_discipline():
    reg = T.MetricsRegistry()
    c = reg.counter('reqs', labels=('kind',))
    c.inc(kind='a')
    c.inc(2, kind='b')
    assert c.value(kind='b') == 2 and c.total() == 3
    with pytest.raises(ValueError, match='got labels'):
        c.inc(wrong='x')
    with pytest.raises(ValueError, match='only go up'):
        c.inc(-1, kind='a')
    g = reg.gauge('depth')
    g.set(7)
    g.set(3)
    assert g.value() == 3
    # re-registration under a different type is a bug, not a new metric
    with pytest.raises(ValueError, match='already registered'):
        reg.gauge('reqs')
    assert 'reqs{kind="b"} 2' in reg.render_prometheus()


# ----------------------------------------------------------------------------
# lifecycle spans from the event log
# ----------------------------------------------------------------------------
def _ev(kind, rid, t, **d):
    return dict(kind=kind, rid=rid, t=t, **d)


def test_span_derivation_clean_and_retry_paths():
    log = [
        # rid 1: one admission, finishes
        _ev('submit', 1, 0.0),
        _ev('admit', 1, 2.0, prefill_s=0.5),
        _ev('finish', 1, 10.0, tokens=5),
        # rid 2: preempted once, re-admitted, finishes
        _ev('submit', 2, 1.0),
        _ev('admit', 2, 3.0, prefill_s=0.25),
        _ev('preempt', 2, 4.0),
        _ev('retry', 2, 4.0),
        _ev('admit', 2, 6.0, prefill_s=0.3),
        _ev('finish', 2, 12.0, tokens=4),
        # rid 3: rejected before any admission
        _ev('submit', 3, 5.0),
        _ev('reject', 3, 5.0),
        # rid 4: no terminal yet -> skipped (the audit owns that case)
        _ev('submit', 4, 6.0),
    ]
    spans = {s.rid: s for s in T.derive_request_spans(log)}
    assert set(spans) == {1, 2, 3}

    s1 = spans[1]
    assert (s1.queue_wait_s, s1.ttft_s, s1.service_s) == (2.0, 2.5, 10.0)
    assert s1.itl_s == pytest.approx((10.0 - 2.5) / 4)
    assert s1.tokens == 5 and s1.admits == 1 and s1.retries == 0

    s2 = spans[2]
    assert s2.admits == 2 and s2.retries == 1 and s2.preempts == 1
    assert s2.queue_wait_s == 2.0              # submit -> FIRST admit
    assert s2.ttft_s == pytest.approx(2.25)    # first admit + its prefill
    assert s2.prefill_s == 0.3                 # LAST admission's prefill
    assert s2.itl_s == pytest.approx((12.0 - 6.3) / 3)

    s3 = spans[3]
    assert s3.terminal == 'reject' and s3.queue_wait_s is None
    assert s3.ttft_s is None and s3.itl_s is None and s3.service_s == 0.0


def test_span_derivation_accepts_live_event_log():
    ticks = iter(float(x) for x in range(100))
    log = faults.EventLog(clock=lambda: next(ticks))
    log.emit('submit', step=0, rid=9)                        # t=0
    log.emit('admit', step=1, rid=9, slot=0)                 # t=1
    log.annotate_last('admit', 9, prefill_s=0.5)
    log.emit('quarantine', step=2, rid=9, slot=0)            # t=2
    log.emit('retry', step=2, rid=9)                         # t=3
    log.emit('admit', step=3, rid=9, slot=1)                 # t=4
    log.emit('finish', step=5, rid=9, tokens=3)              # t=5
    (s,) = T.derive_request_spans(log)
    assert (s.quarantines, s.retries, s.admits) == (1, 1, 2)
    assert s.ttft_s == pytest.approx(1.5) and s.service_s == 5.0
    with pytest.raises(ValueError, match='no .* event for rid'):
        log.annotate_last('admit', 404, prefill_s=1.0)


def test_observe_spans_fills_the_catalog():
    reg = T.MetricsRegistry()
    spans = T.derive_request_spans([
        _ev('submit', 1, 0.0), _ev('admit', 1, 1.0, prefill_s=0.1),
        _ev('finish', 1, 3.0, tokens=4),
        _ev('submit', 2, 0.0), _ev('fail', 2, 9.0),
    ])
    T.observe_spans(reg, spans)
    assert reg.get('serve_requests_total').value(terminal='finish') == 1
    assert reg.get('serve_requests_total').value(terminal='fail') == 1
    assert reg.get('serve_tokens_out_total').value() == 4
    assert reg.get('serve_service_seconds').count == 2
    assert reg.get('serve_ttft_seconds').count == 1   # rid 2 never admitted


# ----------------------------------------------------------------------------
# the timestamp audit (satellite a)
# ----------------------------------------------------------------------------
def test_terminal_accounting_rejects_regressing_timestamps():
    ts = iter([0.0, 5.0, 1.0])
    log = faults.EventLog(clock=lambda: next(ts))
    log.emit('submit', step=0, rid=1)
    log.emit('finish', step=1, rid=1, tokens=1)
    log.emit('submit', step=2, rid=2)          # t jumps backward
    with pytest.raises(ValueError, match='timestamps regress'):
        log.terminal_accounting()


def test_terminal_accounting_rejects_post_terminal_activity():
    log = faults.EventLog()
    log.emit('submit', step=0, rid=1)
    log.emit('finish', step=1, rid=1, tokens=1)
    log.emit('admit', step=2, rid=1, slot=0)   # zombie: not a 2nd terminal
    with pytest.raises(ValueError, match='activity after its terminal'):
        log.terminal_accounting()


# ----------------------------------------------------------------------------
# energy meter == direct hwmodel pricing (no new model, just bookkeeping)
# ----------------------------------------------------------------------------
def test_energy_meter_matches_direct_hwmodel_calls_gqa():
    cfg = configs.get(ARCH, smoke=True)
    meter = T.EnergyMeter(cfg, page_size=4, kv_quant=True, hot_window=2)
    steps = [[(5, 0), (9, 1)], [(6, 0), (10, 1), (14, 2)]]
    for lanes in steps:
        meter.observe_step(lanes)
    want_achieved = want_baseline = want_ops = 0.0
    for s_live, cold in [l for lanes in steps for l in lanes]:
        r = hwmodel.decode_kv_traffic(
            s_live, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.resolved_head_dim, page_size=4, hot_window=2,
            cold_blocks=cold)
        want_achieved += r['tiered_pj_per_token'] * cfg.n_layers
        want_baseline += r['baseline_pj_per_token'] * cfg.n_layers
        want_ops += r['ops_per_token'] * cfg.n_layers
    t = meter.totals()
    assert t['tokens'] == 5 and t['n_attn_layers'] == cfg.n_layers
    assert t['achieved_pj'] == want_achieved           # exact, not approx
    assert t['baseline_pj'] == want_baseline
    assert t['ops'] == want_ops
    assert t['effective_tops_w'] == want_ops / want_achieved
    assert t['achieved_bytes'] < t['baseline_bytes']   # the tier pays off
    assert t['paper']['ima_tops_w'] == pytest.approx(123.8, abs=0.05)


def test_energy_meter_untiered_achieved_equals_baseline():
    cfg = configs.get(ARCH, smoke=True)
    meter = T.EnergyMeter(cfg, page_size=4, kv_quant=False)
    meter.observe_step([(5, 0), (9, 3)])   # cold residency ignored untiered
    t = meter.totals()
    assert t['achieved_bytes'] == t['baseline_bytes'] == t['hot_bytes']
    assert t['cold_bytes'] == 0.0 and t['energy_reduction'] == 1.0


def test_energy_meter_hybrid_layer_split_and_state_term():
    cfg = configs.get('zamba2-1.2b', smoke=True)
    from repro.models.ssm import dims as ssm_dims
    meter = T.EnergyMeter(cfg, page_size=4)
    n_attn = cfg.n_layers // cfg.hybrid_group
    assert (meter.n_attn, meter.n_mamba) == (n_attn, cfg.n_layers - n_attn)
    meter.observe_step([(7, 0)])
    s, dm = cfg.ssm, ssm_dims(cfg)
    st = hwmodel.decode_state_traffic(
        conv_elems=(s.conv_width - 1) * dm['conv_dim'],
        ssm_elems=dm['n_heads'] * s.head_dim * s.d_state,
        n_heads=dm['n_heads'], n_layers=meter.n_mamba)
    kv = hwmodel.decode_kv_traffic(
        7, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.resolved_head_dim, page_size=4, hot_window=1,
        cold_blocks=0)
    t = meter.totals()
    assert t['baseline_pj'] == (kv['baseline_pj_per_token'] * n_attn
                                + st['baseline_pj_per_token'])
    assert t['ops'] == (kv['ops_per_token'] * n_attn + st['ops_per_token'])


def test_hwmodel_cold_blocks_override_clamps():
    kw = dict(n_heads=8, n_kv_heads=4, head_dim=64, page_size=4,
              hot_window=1)
    rule = hwmodel.decode_kv_traffic(17, **kw)              # 5 blocks
    assert rule['cold_blocks'] == 4
    measured = hwmodel.decode_kv_traffic(17, cold_blocks=2, **kw)
    assert (measured['cold_blocks'], measured['hot_blocks']) == (2, 3)
    assert measured['tiered_bytes_per_token'] > \
        rule['tiered_bytes_per_token']   # less int8 residency, more fp bytes
    # out-of-range measurements clamp: the write block is never cold
    assert hwmodel.decode_kv_traffic(17, cold_blocks=99,
                                     **kw)['cold_blocks'] == 4
    assert hwmodel.decode_kv_traffic(17, cold_blocks=-3,
                                     **kw)['cold_blocks'] == 0


# ----------------------------------------------------------------------------
# cross-checks on real serves: metrics can never drift from the audit log
# ----------------------------------------------------------------------------
@pytest.fixture(scope='module')
def clean_out():
    return serve.serve_continuous(ARCH, attn_impl='einsum', quiet=True,
                                  **SMOKE)


@pytest.fixture(scope='module')
def chaos_out():
    inj = faults.FaultInjector(seed=7, profile=faults.chaos_profile())
    return serve.serve_continuous(ARCH, attn_impl='einsum', quiet=True,
                                  faults=inj, retry_budget=16,
                                  kv_quant=True, hot_window=2, **SMOKE)


def _counter_values(snap, name):
    return {k: int(v) for k, v in snap['metrics'][name]['values'].items()}


def test_clean_serve_metrics_equal_event_log(clean_out):
    out = clean_out
    snap = out['telemetry']
    from collections import Counter
    assert _counter_values(snap, 'serve_requests_total') == \
        dict(Counter(out['terminal'].values()))
    assert _counter_values(snap, 'serve_events_total') == out['events']
    assert snap['energy']['tokens'] == out['decode_tokens']
    assert int(snap['metrics']['serve_tokens_out_total']['value']) == \
        sum(out['out_lens'].values())
    assert snap['metrics']['serve_step_seconds']['count'] == out['steps']
    assert snap['spans'] == out['requests']
    # report counts themselves are derived from the log (single source)
    assert out['completed'] == sum(
        1 for v in out['terminal'].values() if v == 'finish')
    s = out['telemetry_summary']
    assert s['ttft_p50_s'] > 0 and s['itl_p50_s'] is not None
    assert s['effective_tops_w'] > 0 and s['paper_ima_tops_w'] == 123.8


def test_chaos_soak_metrics_equal_event_log(chaos_out):
    out = chaos_out
    snap = out['telemetry']
    from collections import Counter
    assert _counter_values(snap, 'serve_requests_total') == \
        dict(Counter(out['terminal'].values()))
    assert _counter_values(snap, 'serve_events_total') == out['events']
    # every applied fault event is counted, by name
    faults_total = sum(
        _counter_values(snap, 'serve_faults_total').values())
    assert faults_total == out['events'].get('fault', 0)
    # tier accounting: quantized pages and cold-byte traffic line up
    assert int(snap['metrics']['serve_pages_quantized_total']['value']) == \
        out['pages_quantized']
    e = snap['energy']
    assert e['kv_quant'] is True
    if out['pages_quantized'] > out['pages_quant_dropped']:
        assert e['cold_bytes'] > 0
        assert e['achieved_pj'] < e['baseline_pj']
    assert e['tokens'] == out['decode_tokens']


def test_no_metrics_run_strips_telemetry():
    out = serve.serve_continuous(ARCH, attn_impl='einsum', quiet=True,
                                 metrics=False, **SMOKE)
    assert 'telemetry' not in out and 'telemetry_summary' not in out
    assert out['completed'] == out['requests']   # accounting still derived


# ----------------------------------------------------------------------------
# trace schema (the --trace surface)
# ----------------------------------------------------------------------------
def test_trace_file_is_loadable_chrome_trace(tmp_path):
    path = str(tmp_path / 'serve.trace.json')
    inj = faults.FaultInjector(seed=7, profile=faults.chaos_profile())
    out = serve.serve_continuous(ARCH, attn_impl='einsum', quiet=True,
                                 faults=inj, retry_budget=16,
                                 trace=path, **SMOKE)
    assert out['trace'] == path
    with open(path) as f:
        tr = json.load(f)
    evs = tr['traceEvents']
    assert {e['ph'] for e in evs} <= {'X', 'i', 'M'}
    for e in evs:
        if e['ph'] == 'X':
            assert e['ts'] >= 0 and e['dur'] >= 0
            assert 0 <= e['tid'] <= SMOKE['slots']
    names = {e['name'] for e in evs if e['ph'] == 'X'}
    assert {'serve.step', 'serve.admit', 'serve.prefill', 'serve.dispatch',
            'serve.wait'} <= names
    # one named track per slot plus the scheduler track
    threads = {e['args']['name'] for e in evs
               if e['ph'] == 'M' and e['name'] == 'thread_name'}
    assert threads == {'scheduler'} | {
        f'slot {s}' for s in range(SMOKE['slots'])}


def test_summarize_none_passthrough():
    assert T.summarize(None) is None
