"""Fast-tier wiring for `make bench-smoke`: the decode benchmark at toy
sizes in interpret mode must run, assert flash-vs-oracle parity, and emit
the decode-bench JSON (smoke runs write BENCH_decode.smoke.json so the
tracked full-size BENCH_decode.json is never clobbered) with the full
three-way (plus paged) comparison."""

import json

from benchmarks import bench_chaos, bench_decode, bench_kv_quant


def test_bench_decode_smoke_writes_parity_checked_json(tmp_path):
    out = tmp_path / 'BENCH_decode.json'
    result = bench_decode.run(smoke=True, out_path=str(out))
    assert out.exists()
    on_disk = json.loads(out.read_text())
    assert on_disk['smoke'] is True
    names = {r['name'] for r in on_disk['rows']}
    assert {'einsum_oracle', 'flash_streamed', 'flash_prefetch',
            'flash_paged', 'mla_einsum_oracle', 'mla_flash_paged',
            'ssm_serve_solo', 'ssm_serve_continuous',
            'hybrid_serve_solo', 'hybrid_serve_continuous'} <= names
    # every flash flavour parity-checked against its family's oracle
    # (run() already asserts; re-check the artifact so a silent tolerance
    # edit fails here); serve rows encode completion in the same field
    for row in result['rows']:
        if not row['name'].endswith('einsum_oracle'):
            assert row['max_abs_err_vs_oracle'] < bench_decode.PARITY_ATOL
    # both requested cache lengths present in the attention sweep (the
    # ssm/hybrid serve rows carry their own prompt+gen s_max)
    attn = {r['s_max'] for r in on_disk['rows'] if '_serve_' not in r['name']}
    assert attn == set(bench_decode.SMOKE_SEQ_LENS)
    # continuous serve rows embed the run's live telemetry summary (PR 8)
    for row in on_disk['rows']:
        if row['name'].endswith('_serve_continuous'):
            t = row['telemetry']
            assert t['tokens'] > 0
            assert t['effective_tops_w'] is not None
            assert t['itl_p50_s'] is not None


def test_bench_kv_quant_smoke_asserts_quantized_path(tmp_path):
    """The hybrid-tier benchmark in the fast tier: the GQA q8 kernel, the
    MLA latent-tier kernel, and their tier-mixing oracles parity-gated
    against the f32 oracles, traffic models emitted for both families."""
    out = tmp_path / 'BENCH_kv_quant.json'
    result = bench_kv_quant.run(smoke=True, out_path=str(out))
    assert out.exists()
    on_disk = json.loads(out.read_text())
    assert on_disk['smoke'] is True
    names = {r['name'] for r in on_disk['rows']}
    assert {'einsum_oracle_f32', 'flash_paged_fp', 'einsum_q8_tier',
            'flash_paged_q8', 'mla_einsum_oracle_f32', 'mla_flash_paged_fp',
            'mla_einsum_q8_tier', 'mla_flash_paged_q8'} <= names
    for row in result['rows']:
        if 'oracle' in row['name']:
            continue
        assert row['max_abs_err_vs_oracle'] < \
            bench_kv_quant.parity_atol_for(row['name'])
    # traffic rows carry the hwmodel energy breakdown for both baselines
    # and both cache families (GQA K/V pools + MLA latent pool)
    assert {(t['family'], t['baseline']) for t in on_disk['traffic']} == \
        {('gqa', 'f32_oracle'), ('gqa', 'bf16_pool'),
         ('mla', 'f32_oracle'), ('mla', 'bf16_pool')}
    for t in on_disk['traffic']:
        assert t['tiered_bytes_per_token'] <= t['baseline_bytes_per_token']
        assert 'tiered_pj_per_token' in t and 'tiered_tops_w' in t


def test_bench_chaos_smoke_asserts_accounting(tmp_path):
    """The robustness benchmark in the fast tier: clean run completes the
    stream in one compilation; the seeded chaos run reaches a terminal
    state for every rid and still completes the floor fraction (run()
    already gates; re-check the artifact so a silent edit fails here)."""
    out = tmp_path / 'BENCH_chaos.json'
    result = bench_chaos.run(smoke=True, out_path=str(out))
    assert out.exists()
    on_disk = json.loads(out.read_text())
    assert on_disk['smoke'] is True
    rows = {r['label']: r for r in on_disk['rows']}
    assert {'clean', 'chaos_default_profile', 'chaos_kv_quant'} <= set(rows)
    clean = rows['clean']
    assert clean['completed'] == clean['requests']
    assert clean['decode_compilations'] == 1
    for label in ('chaos_default_profile', 'chaos_kv_quant'):
        r = rows[label]
        n_term = (r['completed'] + r['failed'] + r['rejected']
                  + r['cancelled'])
        assert n_term == r['requests']
        assert r['completed'] >= bench_chaos.COMPLETION_FLOOR * r['requests']
    assert on_disk['step_overhead'] >= 1.0
    assert result['rows'][0]['label'] == 'clean'
    # PR 8: every row embeds its telemetry summary; the metrics tax is
    # measured (and budget-gated inside run() on the smoke tier) and the
    # emitted trace validated as loadable Chrome-trace JSON
    for r in on_disk['rows']:
        assert r['telemetry']['ttft_p50_s'] is not None
        assert r['telemetry']['paper_ima_tops_w'] == 123.8
    mo = on_disk['metrics_overhead']
    assert mo['overhead_frac'] < mo['budget']
    assert mo['bare_step_s'] > 0 and mo['instrumented_step_s'] > 0
    assert on_disk['trace']['trace_events'] > 0
    assert {'serve.step', 'serve.prefill', 'serve.dispatch'} <= \
        set(on_disk['trace']['span_names'])
