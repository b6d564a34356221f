"""The program's own host spans in the profiler trace of a ``--trace 1``
run: the ``serve.*`` phases that ``serve_continuous`` annotates
(``repro.runtime.telemetry.Span``), nested by time on their thread, on the
clock of the device events.

The profiler records a span only when it was on at both of its ends, so
every span the trace holds lies wholly inside the tracer's window: the
loop step that starts the profiler and the one that stops it leave no
``serve.step`` (some of their inner phases may remain, with no step
around them). A program that annotates nothing, as before these spans,
gives an empty list, and every reader here returns ``None``.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

from chipbench.harness import trace as trace_mod

PREFIX = 'serve.'
STEP = 'serve.step'
WAIT = 'serve.wait'
ADMIT = 'serve.admit'
#: idle time outside every loop step (between steps, or untraced)
OUTSIDE = 'outside serve.step'
_CTX_KEY = 'serve_spans'


@dataclasses.dataclass
class Span:
    name: str
    start_ns: float
    end_ns: float
    children: List['Span'] = dataclasses.field(default_factory=list)

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9

    def walk(self):
        yield self
        for c in self.children:
            yield from c.walk()


def collect(pd) -> List[Span]:
    """The ``serve.*`` spans of a loaded trace (``trace.load``) as trees:
    the roots of every host thread, each holding the spans nested in it,
    in time order."""
    roots: List[Span] = []
    for pl in pd.planes:
        if not pl.name.startswith('/host:'):
            continue
        for ln in pl.lines:
            evs = sorted((ev.start_ns, -ev.end_ns, ev.name)
                         for ev in ln.events if ev.name.startswith(PREFIX))
            stack: List[Span] = []
            for start, neg_end, name in evs:
                sp = Span(name, start, -neg_end)
                while stack and stack[-1].end_ns <= start:
                    stack.pop()
                (stack[-1].children if stack else roots).append(sp)
                stack.append(sp)
    return sorted(roots, key=lambda s: s.start_ns)


def load(ctx) -> List[Span]:
    """The traced run's spans, read from ``ctx['tracer'].outdir`` once per
    run; an empty list where there is no trace or no span."""
    if _CTX_KEY not in ctx:
        tr = ctx.get('tracer')
        spans: List[Span] = []
        if tr is not None and tr.t0 is not None and tr.outdir.is_dir():
            spans = collect(trace_mod.load(tr.outdir))
        ctx[_CTX_KEY] = spans
    return ctx[_CTX_KEY]


def named(roots: List[Span], name: str) -> List[Span]:
    return [s for r in roots for s in r.walk() if s.name == name]


def host_ms(roots: List[Span]) -> Optional[float]:
    """Mean over the whole ``serve.step`` spans of each one's duration
    less the ``serve.wait`` spans nested anywhere in it: the host's own
    time per loop step, in ms."""
    steps = named(roots, STEP)
    if not steps:
        return None
    own = [s.seconds - sum(w.seconds for w in named([s], WAIT))
           for s in steps]
    return 1e3 * sum(own) / len(own)


def admit_ms(roots: List[Span]) -> Optional[float]:
    """Mean duration of one admission (``serve.admit``), in ms."""
    adm = named(roots, ADMIT)
    return 1e3 * sum(s.seconds for s in adm) / len(adm) if adm else None


def innermost(roots: List[Span], start: float, end: float) -> str:
    """The innermost span that covers most of ``[start, end)``: at each
    depth the gap's part goes to whichever covers the most of it, one of
    the spans there or the enclosing span's own time (``OUTSIDE`` at the
    top), descending until the enclosing span's own time wins."""
    label, level, own = OUTSIDE, roots, end - start
    while True:
        best, cover, inner = None, 0.0, 0.0
        for s in level:
            ov = min(end, s.end_ns) - max(start, s.start_ns)
            if ov > 0:
                inner += ov
                if ov > cover:
                    best, cover = s, ov
        if best is None or cover <= own - inner:
            return label
        label, level, own = best.name, best.children, cover
        start, end = max(start, best.start_ns), min(end, best.end_ns)


def idle_by_phase(roots: List[Span], gaps) -> Dict[str, float]:
    """Seconds of device idle time by the phase that held the host: each
    gap (``trace.reduce``'s ``gaps``, ns on the trace's clock) goes whole
    to :func:`innermost`. ``serve.step`` is the step's own time outside
    every inner phase."""
    out: Dict[str, float] = {}
    for start, end in gaps:
        k = innermost(roots, start, end)
        out[k] = out.get(k, 0.0) + (end - start) * 1e-9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def idle_split(roots: List[Span], gaps) -> Dict[str, float]:
    """Seconds of device idle time by phase, split exactly: each
    nanosecond of a gap goes to the innermost span that covers it
    (``serve.step`` its own time, ``OUTSIDE`` what no span covers)."""
    out: Dict[str, float] = {}

    def covered(level, start, end) -> float:
        total = 0.0
        for s in level:
            lo, hi = max(start, s.start_ns), min(end, s.end_ns)
            if hi > lo:
                own = (hi - lo) - covered(s.children, lo, hi)
                out[s.name] = out.get(s.name, 0.0) + own * 1e-9
                total += hi - lo
        return total

    for start, end in gaps:
        rest = (end - start) - covered(roots, start, end)
        if rest > 0:
            out[OUTSIDE] = out.get(OUTSIDE, 0.0) + rest * 1e-9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))
