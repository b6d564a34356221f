"""What the metric files under ``chipbench/metrics`` compute: each file
is one metric's reader (``read(ctx)``), most of them a call into here.

``ctx`` carries the load generator (requests, token times, per-step records, the
window), the configuration, the deployment, the trace summary of a
``--trace 1`` run with its tracer, and the device's peaks. A reader that
finds nothing to read returns ``None``, and the metric is left out.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from chipbench.harness import counts, trace as trace_mod


def _window(ctx):
    d = ctx['load']
    return d.t_open, d.t_close


def host_window_s(ctx) -> float:
    """The window on the host clock, less the profiler's start-up."""
    t0, t1 = _window(ctx)
    tr = ctx.get('tracer')
    return (t1 - t0) - (tr.start_cost if tr is not None else 0.0)


def window_steps(ctx) -> list:
    t0, t1 = _window(ctx)
    return [s for s in ctx['load'].steps if t0 < s.t <= t1]


def traced_steps(ctx) -> list:
    tr = ctx.get('tracer')
    if tr is None or tr.t0 is None:
        return []
    return [s for s in ctx['load'].steps if tr.t0 < s.t <= tr.t1]


def p95(values) -> Optional[float]:
    v = np.asarray(list(values), float)
    return float(np.percentile(v, 95)) if v.size else None


def itl_s(ctx) -> List[float]:
    out = []
    for r in ctx['load'].window_records():
        if r.status == 'done':
            out += list(np.diff(r.times))
    return out


def output_tok_s(ctx) -> Optional[float]:
    t0, t1 = _window(ctx)
    n = sum(s.tokens for s in window_steps(ctx))
    return n / (t1 - t0) if n else None


def step_ms(ctx) -> Optional[float]:
    n = len(window_steps(ctx))
    return host_window_s(ctx) / n * 1e3 if n else None


def batch(ctx) -> Optional[float]:
    st = [len(s.ctx) for s in window_steps(ctx) if s.ctx]
    return float(np.mean(st)) if st else None


def program_dev_ms(ctx, pattern: str) -> Optional[float]:
    """Mean device time of one run of the program whose module name
    matches ``pattern``, from the trace."""
    summ = ctx.get('trace')
    if not summ:
        return None
    import re
    rx = re.compile(pattern)
    d = [t for name, ts in summ['programs'].items() if rx.search(name)
         for t in ts]
    return float(np.mean(d)) * 1e3 if d else None


def idle_pct(ctx) -> Optional[float]:
    summ, tr = ctx.get('trace'), ctx.get('tracer')
    if not summ or tr is None or tr.t1 is None or summ['busy_s'] <= 0:
        return None
    return 100.0 * (1.0 - summ['busy_s'] / (tr.t1 - tr.t0))


def roofline_pct(ctx, pattern: str, flops: float, byts: float,
                 flop_peak: str) -> Optional[float]:
    """Least time the chip could take for the work (the larger of
    operations over peak and bytes over bandwidth) over the summed device
    time of the kernel's events, in percent."""
    summ, pk = ctx.get('trace'), ctx.get('peaks')
    if not summ or not pk or not flops:
        return None
    n, t = trace_mod.kernel_seconds(summ, pattern)
    if not n or t <= 0:
        return None
    t_min = max(flops / pk[flop_peak], byts / pk['hbm_bytes_per_s'])
    return 100.0 * t_min / t


def decode_attn_work(ctx):
    f = b = 0.0
    for s in traced_steps(ctx):
        ff, bb = counts.decode_attn(ctx['config'], ctx['serving'], s.ctx)
        f += ff
        b += bb
    return f, b


def int8_matmul_work(ctx):
    c = ctx['config']
    calls = []
    for s in traced_steps(ctx):
        if s.ctx:
            calls += counts.matmul_calls(c, len(s.ctx), len(s.ctx))
        for p in s.prefills:
            calls += counts.matmul_calls(c, p, 1)
    return counts.int8_matmul(calls)


def mfu_pct(ctx) -> Optional[float]:
    steps = window_steps(ctx)
    fl = sum(counts.model_flops(ctx['config'], s.ctx, s.prefills)
             for s in steps)
    if not fl or ctx.get('peaks') is None:
        return None
    peak = counts.peak_rate(ctx['serving'], ctx['peaks'])
    return 100.0 * fl / (host_window_s(ctx) * peak)
