"""Reduction of a JAX profiler trace (``.xplane.pb``) to busy and idle
time, per-program and per-kernel device time.

Read with ``jax.profiler.ProfileData`` alone. A TPU's plane is
``/device:TPU:<n>``; its ``XLA Modules`` line holds one event per
program run (``jit_<name>(<id>)``) and its ``XLA Ops`` line one event per
operation (``%<op> = <shape> <opcode>(...)``), control flow
included: a scanned layer stack is a ``while`` op that spans the ops of
its body. A Pallas kernel is a custom call named after the jitted wrapper
that launched it (``flash_decode_gqa_paged``, ``int8_matmul``). Host
planes hold the harness's own ``TraceAnnotation`` spans, on the same
clock.

Busy time is the union of program intervals on a device, averaged over
the devices traced; idle gaps are the spaces between, labelled by the
host annotation that covers most of each gap. Op time is grouped by
HLO op name less its numeric suffix (every call of one kernel, every
``copy``), control-flow containers left out.
"""

from __future__ import annotations

import collections
import pathlib
import re
from typing import Dict, List, Optional, Tuple

UNANNOTATED = 'serve loop (unannotated)'


def find_xplane(trace_dir) -> pathlib.Path:
    hits = sorted(pathlib.Path(trace_dir).rglob('*.xplane.pb'))
    if not hits:
        raise FileNotFoundError(f'no .xplane.pb under {trace_dir}')
    return hits[-1]


def load(path):
    from jax.profiler import ProfileData
    p = pathlib.Path(path)
    if p.is_dir():
        p = find_xplane(p)
    return ProfileData.from_file(str(p))


def _merge(iv: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


_MODULE_ID = re.compile(r'\(\d+\)$')


def module_base(name: str) -> str:
    """``jit_decode_step(123)`` -> ``jit_decode_step``."""
    return _MODULE_ID.sub('', name)


_CONTAINERS = ('while', 'conditional', 'call')


_SUFFIX = re.compile(r'\.\d+$')


def op_label(name: str) -> str:
    """An op event's HLO op name less its numeric suffix
    (``%flash_decode_gqa_paged.9 = f32[...] custom-call(...)`` ->
    ``flash_decode_gqa_paged``)."""
    return _SUFFIX.sub('', name.split(' = ', 1)[0].lstrip('%'))


def reduce(pd, window_ns: Optional[Tuple[int, int]] = None) -> dict:
    """Summary of one trace: per device busy/idle, programs and ops.

    ``window_ns`` limits every device event to that interval (the trace's
    own clock); without it the window is the trace's span on the device.
    Returns ``busy_s`` and ``window_s`` averaged over devices, per-program
    lists of device durations (``programs``), per-op totals (``ops``: label
    -> [count, seconds]), idle gaps and host spans."""
    devices = [pl for pl in pd.planes if pl.name.startswith('/device:')
               and 'TPU' in pl.name]
    hosts = [pl for pl in pd.planes if pl.name.startswith('/host:')]
    programs: Dict[str, List[float]] = collections.defaultdict(list)
    ops: Dict[str, List[float]] = collections.defaultdict(lambda: [0, 0.0])
    busy_total = window_total = 0.0
    gaps: List[Tuple[int, int]] = []
    for pl in devices:
        lines = {ln.name: ln for ln in pl.lines}
        op_line = lines.get('XLA Ops')
        mod_line = lines.get('XLA Modules')
        iv = []
        for ev in (op_line.events if op_line is not None else ()):
            if window_ns and (ev.end_ns <= window_ns[0]
                              or ev.start_ns >= window_ns[1]):
                continue
            label = op_label(ev.name)
            if label in _CONTAINERS:
                continue
            rec = ops[label]
            rec[0] += 1
            rec[1] += ev.duration_ns * 1e-9
        for ev in (mod_line.events if mod_line is not None else ()):
            if window_ns and (ev.end_ns <= window_ns[0]
                              or ev.start_ns >= window_ns[1]):
                continue
            programs[module_base(ev.name)].append(ev.duration_ns * 1e-9)
            iv.append((ev.start_ns, ev.end_ns))
        merged = _merge(iv)
        if window_ns:
            lo, hi = window_ns
            merged = [(max(s, lo), min(e, hi)) for s, e in merged]
        elif merged:
            lo, hi = merged[0][0], merged[-1][1]
        else:
            lo = hi = 0
        busy_total += sum(e - s for s, e in merged) * 1e-9
        window_total += (hi - lo) * 1e-9
        edges = [lo] + [x for iv_ in merged for x in iv_] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    host_spans = []
    for pl in hosts:
        for ln in pl.lines:
            for ev in ln.events:
                if ev.name.startswith('bench.'):
                    host_spans.append((ev.start_ns, ev.end_ns, ev.name))
    n = max(len(devices), 1)
    return dict(n_devices=len(devices), busy_s=busy_total / n,
                window_s=window_total / n, programs=dict(programs),
                ops={k: list(v) for k, v in ops.items()},
                gaps=gaps, host_spans=host_spans)


def label_gaps(summary: dict, top: int = 10) -> List[List]:
    """The longest idle gaps, each named by the host span that covers
    most of it (the harness's annotations), else as the unannotated serve
    loop."""
    spans = summary['host_spans']
    out = []
    for s, e in sorted(summary['gaps'], key=lambda g: g[0] - g[1])[:top]:
        best, cover = UNANNOTATED, 0
        for hs, he, name in spans:
            ov = min(e, he) - max(s, hs)
            if ov > cover:
                best, cover = name, ov
        out.append([best, (e - s) * 1e-9])
    return out


def top_ops(summary: dict, top: int = 10) -> List[List]:
    items = sorted(summary['ops'].items(), key=lambda kv: -kv[1][1])[:top]
    return [[k, v[1]] for k, v in items]


def kernel_seconds(summary: dict, pattern: str) -> Tuple[int, float]:
    """(events, seconds) of the ops whose label matches ``pattern``."""
    rx = re.compile(pattern)
    n, t = 0, 0.0
    for k, (c, s) in summary['ops'].items():
        if rx.search(k):
            n += c
            t += s
    return n, t
