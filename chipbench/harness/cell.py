"""One run of one cell: load its files, check the device, serve through
``repro.launch.serve.serve_continuous`` under the open-loop load, judge the
output against the plain reference, read the metrics, print one line.

Every file of a cell is found by a name in ``BENCHMARK.json``:

- ``chipbench/configs/<config>.json`` (its path is the config's ``file``):
  the model as run and its deployment (``serving``), the reference's
  initialisation and the limit of the correctness comparison;
- ``chipbench/traffic/<traffic>.json``: the mix, read by ``traffic.py``;
- ``chipbench/cells/<workload>.json``: the cell's slots and rate;
- ``chipbench/metrics/<metric>.py``: the metric's reader, ``read(ctx)``
  returning a number or ``None`` (nothing to read: the metric is left
  out). A name with dotted parts falls back to the reader of its longest
  dotted prefix that has a file, so ``device.idle.chat`` and
  ``device.idle.w8a8`` share ``device.idle.py``;
- ``chipbench/arch/<model_type>.py`` (named by the ``model_type`` in the
  configuration's ``model``): the architecture's ``ArchConfig``, plain
  reference and counts, as ``harness/arch.py`` states.

So the cell of a new architecture adds files and edits none: its
``arch/<model_type>.py``, ``configs/<config>.json`` and
``cells/<cell>.json``; a ``traffic/<mix>.json`` or ``metrics/<metric>.py``
only where no file there serves; and its entries in ``BENCHMARK.json``.
"""

from __future__ import annotations

import gc
import hashlib
import importlib.util
import inspect
import json
import pathlib
import shutil
import sys
import time
from typing import Optional

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / 'chipbench'
TRACE_DIR = BENCH / '_trace'
TRACE_SECONDS = 6.0
SAMPLE_MIN_LANES = 4


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def program_seed(seed: int) -> int:
    """The program's PRNG seed: JAX keeps only 32 bits of an integer seed,
    so a large ``--seed`` is hashed to 31 bits rather than cut."""
    h = hashlib.sha256(str(int(seed)).encode()).digest()
    return int.from_bytes(h[:8], 'little') % (2 ** 31)


def load_cell(name: str, root: pathlib.Path = ROOT) -> dict:
    bench = json.loads((root / 'BENCHMARK.json').read_text())
    cells = {w['name']: w for w in bench['workloads']}
    if name not in cells:
        raise KeyError(f'no workload {name!r} in BENCHMARK.json')
    w = cells[name]
    conf_entry = {c['name']: c for c in bench['configs']}[w['config']]
    return dict(
        bench=bench, workload=w,
        config=json.loads((root / conf_entry['file']).read_text()),
        mix=json.loads((root / 'chipbench' / 'traffic'
                        / f"{w['traffic']}.json").read_text()),
        params=json.loads((root / 'chipbench' / 'cells'
                           / f'{name}.json').read_text()))


def metrics_for(bench: dict, cell: str, trace: bool) -> list:
    """The cell's end-to-end metrics (``trace`` off: those that list it,
    or list no cells) or per-layer metrics (``trace`` on: those that list
    it)."""
    if trace:
        return [m for m in bench['per_layer'] if cell in m['workloads']]
    return [m for m in bench['end_to_end']
            if cell in m.get('workloads', [cell])]


def reader_path(name: str) -> pathlib.Path:
    """``metrics/<name>.py``, else the file of the longest dotted prefix
    of the name that has one."""
    parts = name.split('.')
    for n in range(len(parts), 0, -1):
        path = BENCH / 'metrics' / ('.'.join(parts[:n]) + '.py')
        if path.is_file():
            return path
    raise FileNotFoundError(f'no reader for the metric {name!r}')


def read_metric(name: str, ctx: dict):
    path = reader_path(name)
    spec = importlib.util.spec_from_file_location(
        'chipbench_metric_' + name.replace('.', '_').replace('-', '_'), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def arch_config(conf: dict):
    """The program's ``ArchConfig`` for a configuration file, built by its
    architecture's module and registered under the configuration's name so
    ``serve_continuous`` can look it up."""
    from repro.configs import base
    from chipbench.harness import arch
    cfg = arch.of(conf['model']).arch_config(conf)
    base.register(cfg, cfg)
    return cfg


class CompileCounter:
    """Counts executables JAX builds or loads from its persistent cache
    (``backend_compile_duration`` wraps both)."""

    def __init__(self):
        import jax.monitoring
        from jax._src import dispatch
        self.event = dispatch.BACKEND_COMPILE_EVENT
        self.count = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event, duration, **kw):
        if event == self.event:
            self.count += 1

    def _ev(self, event, **kw):
        if event == '/jax/compilation_cache/cache_hits':
            self.hits += 1


class GcWatch:
    """Times the interpreter's garbage collections, to tell a pause of
    the loop that is a collection from one that is not."""

    def __init__(self, clock=time.perf_counter):
        self.clock = clock
        self.runs = []                     # (start, seconds, generation)
        self._t0 = None
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == 'start':
            self._t0 = self.clock()
        elif self._t0 is not None:
            self.runs.append((self._t0, self.clock() - self._t0,
                              info['generation']))
            self._t0 = None

    def close(self):
        gc.callbacks.remove(self._cb)

    def summary(self, t0: float, t1: float) -> dict:
        """Collections that started in ``[t0, t1]``, and a full collection
        timed now, with the count of objects it had to walk."""
        inw = [r for r in self.runs if t0 <= r[0] <= t1]
        full = [r[1] for r in inw if r[2] == 2]
        n_obj = len(gc.get_objects())
        t = self.clock()
        gc.collect()
        return dict(in_window=len(inw),
                    in_window_s=float(sum(r[1] for r in inw)),
                    full_in_window=len(full),
                    longest_s=float(max((r[1] for r in inw), default=0.0)),
                    full_after_close_s=self.clock() - t,
                    tracked_objects=n_obj)


class Tracer:
    """Profiles the last ``TRACE_SECONDS`` of the window, starting and
    stopping at step boundaries (the loop has waited for the step)."""

    def __init__(self, outdir: pathlib.Path, clock=time.perf_counter):
        self.outdir = outdir
        self.clock = clock
        self.t0 = self.t1 = None
        self.start_cost = 0.0

    def annotate(self, name: str):
        import jax
        return jax.profiler.TraceAnnotation(name)

    def at_step(self, now: float, t_open: float, seconds: float) -> None:
        import jax
        if self.t0 is None and \
                now >= t_open + max(0.0, seconds - TRACE_SECONDS):
            shutil.rmtree(self.outdir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # host spans, no Python calls
            jax.profiler.start_trace(str(self.outdir),
                                     profiler_options=opts)
            self.t0 = self.clock()
            self.start_cost = self.t0 - now

    def stop(self, now: float) -> None:
        import jax
        if self.t0 is not None and self.t1 is None:
            self.t1 = now
            jax.profiler.stop_trace()


def check_device(chips: int) -> dict:
    import jax
    devs = jax.devices()
    if devs[0].platform == 'cpu':
        raise NoDevice('JAX found no accelerator (platform cpu)')
    if len(devs) < chips:
        raise NoDevice(f'the cell asks for {chips} chips; JAX sees '
                       f'{len(devs)}')
    return dict(platform=devs[0].platform, kind=devs[0].device_kind,
                count=chips)


def configure_jax() -> None:
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    # cache every program, the small eager ones too, so that only the
    # first run of a cell in a checkout compiles
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)


def info(**kw) -> None:
    print(json.dumps(dict(info=kw)), flush=True)


def sample_requests(recs, seed: int, slots: int):
    """Served requests for the reference, one to a lane: the one served
    the most tokens, then a seed-drawn request from each of further lanes
    in a seed-drawn order, until half of the lanes (at least
    ``SAMPLE_MIN_LANES``) are covered, so a fault in some lanes is seen."""
    recs = sorted(recs, key=lambda r: (-len(r.tokens), r.rid))
    if not recs:
        return []
    rng = np.random.default_rng(int(seed) % 2 ** 63)
    by_lane = {}
    for r in recs[1:]:
        by_lane.setdefault(r.slot, []).append(r)
    by_lane.pop(recs[0].slot, None)
    lanes = list(by_lane.values())
    want = max(SAMPLE_MIN_LANES, -(-int(slots) // 2))
    out = [recs[0]]
    for i in rng.permutation(len(lanes)):
        if len(out) >= want:
            break
        out.append(lanes[i][rng.integers(len(lanes[i]))])
    return out


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, cell: Optional[dict] = None,
             require_chip: bool = True, serve_fn=None,
             control: Optional[str] = None) -> dict:
    """One run. ``cell``, ``serve_fn`` and ``control`` are for the
    harness's own tests and control readings (a smaller configuration on
    the CPU, a program broken on purpose, the lower-precision reference's
    reading beside the program's); the command line sets none of them."""
    cell = cell if cell is not None else load_cell(name)
    w, conf, mix, cp = (cell['workload'], cell['config'], cell['mix'],
                        cell['params'])
    src = ROOT / 'src'
    if not (src / 'repro').is_dir():
        raise FileNotFoundError(f'the program is not at {src}')
    sys.path.insert(0, str(src))
    import jax
    configure_jax()
    device = (check_device(int(w['chips'])) if require_chip else
              dict(platform=jax.devices()[0].platform,
                   kind=jax.devices()[0].device_kind, count=1))
    from repro.launch import serve as serve_mod
    from chipbench.harness import correct, loadgen, traffic as trf

    cfg = arch_config(conf)
    # so are the page size and hot window where the file states none
    defaults = inspect.signature(serve_mod.serve_continuous).parameters
    serving = dict(conf['serving'], page_size=defaults['page_size'].default)
    serving.setdefault('hot_window', defaults['hot_window'].default)
    pseed = program_seed(seed)
    tr = trf.Traffic(mix, seed=seed, vocab=cfg.vocab_size,
                     rate_per_s=cp.get('rate_per_s', 0.0), seconds=seconds)
    counter = CompileCounter()
    gcw = GcWatch()
    tracer = Tracer(TRACE_DIR / w['name']) if trace else None
    d = loadgen.OpenLoop(
        tr, seconds=seconds, slots=cp['slots'],
        make_request=lambda rid, p, g: serve_mod.Request(
            rid=rid, prompt=np.asarray(p, np.int32), target_gen=int(g)),
        warm_steps=int(mix.get('warm_steps', 2)), tracer=tracer,
        memory_in_use=lambda: (jax.devices()[0].memory_stats() or {}).get(
            'bytes_in_use', 0))
    d.compile_counter = counter
    stream = d.initial_stream()
    serve = serve_fn or serve_mod.serve_continuous
    out = serve(conf['name'], smoke=False, slots=cp['slots'],
                prompt_len=tr.max_prompt, gen_len=tr.max_output,
                mode=serving['mode'], prequantize=serving['prequantize'],
                kv_quant=serving['kv_quant'],
                hot_window=serving['hot_window'], seed=pseed,
                attn_impl='flash', request_stream=stream,
                max_steps=10 ** 9, step_hook=d.hook, quiet=True)
    if d.t_open is None or d.t_close is None:
        raise RuntimeError('the run ended before its window closed')
    compiles_in_window = counter.count - d.compiles_at_open
    gcw.close()
    stats = jax.devices()[0].memory_stats() or {}
    mem_peak = int(stats.get('peak_bytes_in_use', 0))
    setup_s = d.t_open - t_start
    lat = d.lateness()
    info(compiles_in_window=compiles_in_window,
         compiles_total=counter.count, cache_hits=counter.hits,
         generator_late_ms=dict(
             mean=float(lat.mean() * 1e3) if lat.size else 0.0,
             p95=float(np.percentile(lat, 95) * 1e3) if lat.size else 0.0,
             max=float(lat.max() * 1e3) if lat.size else 0.0),
         steps=len(d.steps), window_s=d.t_close - d.t_open,
         longest_step_s=float(np.max(np.diff([d.t_open] + [
             s.t for s in d.steps]))) if d.steps else None,
         backlog_at_close=d.backlog_at_close,
         gc=gcw.summary(d.t_open, d.t_close),
         bytes_in_use_at_close=d.bytes_in_use_at_close,
         memory_peak_bytes=mem_peak,
         program=dict(completed=out['completed'], failed=out['failed'],
                      cancelled=out['cancelled'],
                      decode_compilations=out['decode_compilations'],
                      pages_quantized=out['pages_quantized'],
                      peak_pages=out['peak_pages']))
    del out
    if tr.open_loop:
        # the first-token tail is printed, not judged: single stalls of
        # the loop set it over a window's few requests (PERF.md section 2)
        ttft = np.array([r.times[0] - r.due for r in d.window_records()
                         if r.times]) * 1e3
        if ttft.size:
            info(ttft_ms=dict(p50=float(np.percentile(ttft, 50)),
                              p95=float(np.percentile(ttft, 95)),
                              n=int(ttft.size)))

    summary = None
    if trace and tracer.t0 is not None:
        from chipbench.harness import trace as trace_mod
        summary = trace_mod.reduce(trace_mod.load(TRACE_DIR / w['name']))
        traced = [s for s in d.steps if tracer.t0 < s.t <= tracer.t1]
        info(traced=dict(seconds=tracer.t1 - tracer.t0, steps=len(traced),
                         decode_steps=sum(bool(s.ctx) for s in traced),
                         prefills=sum(len(s.prefills) for s in traced),
                         start_cost_s=tracer.start_cost,
                         programs={k: len(v) for k, v in
                                   summary['programs'].items()}))

    # -- correctness: the served tokens against the plain reference ---------
    recs = [r for r in d.recs.values() if r.tokens and not r.warm
            and _served_in(r, d)]
    sample = sample_requests(recs, seed, cp['slots'])
    t_ref = time.perf_counter()
    n_before_ref = counter.count
    served, ctrl = correct.gaps(pseed, conf['model'], sample,
                                pad_to=tr.max_prompt + tr.max_output,
                                control=control)
    program_gap = float(np.max(served)) if sample else float('inf')
    # the control: the lower precision's picks stand in for the served
    # tokens, and are judged alike
    gap = float(np.max(ctrl)) if control and sample else program_gap
    ref_s = time.perf_counter() - t_ref
    limit = float(conf['correct']['max_logit_gap'])
    ok = bool(sample) and gap <= limit and compiles_in_window == 0
    info(reference_s=ref_s, reference_compiles=counter.count - n_before_ref,
         sampled_requests=len(sample),
         sampled_tokens=int(sum(len(r.tokens) for r in sample)))

    ctx = dict(load=d, config=conf['model'], serving=serving, cell=w,
               params=cp, seconds=seconds, setup_s=setup_s,
               device=device, trace=summary, tracer=tracer,
               peaks=None)
    from chipbench.harness import counts
    if require_chip:
        ctx['peaks'] = counts.peaks(device['kind'])
    metrics = {}
    for m in metrics_for(cell['bench'], w['name'], trace):
        v = read_metric(m['name'], ctx)
        if v is not None:
            metrics[m['name']] = dict(value=float(v), unit=m['unit'])
    attempted, failed = _attempts(d, tr)
    device = dict(device, memory_peak_bytes=mem_peak,
                  bytes_in_use_at_close=d.bytes_in_use_at_close)
    result = dict(correct=ok, attempted=attempted, failed=failed,
                  metrics=metrics, device=device)
    if summary is not None:
        from chipbench.harness import trace as trace_mod
        device['busy_s'] = summary['busy_s']
        device['window_s'] = (tracer.t1 - tracer.t0)
        result['breakdown'] = dict(
            device_ops=trace_mod.top_ops(summary),
            idle_gaps=trace_mod.label_gaps(summary))
        shutil.rmtree(TRACE_DIR / w['name'], ignore_errors=True)
    if control:
        result['program_gap'] = program_gap
    result['compared'] = dict(
        max_logit_gap=dict(value=gap, limit=limit),
        compiles_in_window=dict(value=compiles_in_window, limit=0))
    return result


def _served_in(rec, d) -> bool:
    """A request the window served: due in it and finished (draining
    mixes), or with a token delivered in it, finished or cut at the close
    (cancelling mixes)."""
    if d.traffic.drains:
        return rec.in_window and rec.status == 'done'
    return any(d.t_open < t <= d.t_close for t in rec.times)


def _attempts(d, tr):
    if tr.drains:
        recs = d.window_records()
        return len(recs), sum(r.status != 'done' for r in recs)
    recs = [r for r in d.recs.values()
            if any(d.t_open < t <= d.t_close for t in r.times)
            or r.status == 'failed']
    return len(recs), sum(r.status == 'failed' for r in recs)
