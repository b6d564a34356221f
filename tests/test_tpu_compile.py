"""Compile-only checks of every Pallas kernel entrypoint for a described
TPU v5e, at the widths the serving path runs them: stablelm-1.6b
(Hkv = 32, dh = 64, and its Hkv = 8 shard under 4-way TP) and the
deepseek-v3 latent pool (r + d_rope = 576, 128 heads).

Interpret mode (every other kernel test) cannot see Mosaic's tiling and
VMEM rules; the TPU compiler that ships with jaxlib can, without a chip.
The topology is described inside a module fixture — never at import time
— so every test worker collects the same tests and only the one that runs
this file loads the TPU library."""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import flash_decode as fd
from repro.kernels import ops
from repro.kernels import quantize, yoco_vmm

BF, I8, I32, F32 = jnp.bfloat16, jnp.int8, jnp.int32, jnp.float32
B, PS, W = 4, 16, 10            # slots, page size, block-table width
P = 1 + B * W                   # pool pages incl. the garbage page
DH = 64                         # stablelm-1.6b head dim
R, DR, HM = 512, 64, 128        # deepseek-v3 latent rank, rope dim, heads


@pytest.fixture(scope='module')
def topo():
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:      # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f'no v5e:2x2 topology can be described here: {e}')


@pytest.fixture(scope='module')
def compile_tpu(topo):
    """``compile_tpu(fn, *(shape, dtype), **jit_kwargs)`` -> compiled HLO
    text of ``fn`` for one described v5e chip (shapes only; nothing runs).
    The persistent compilation cache stays off: a described-chip compile
    cannot be read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()

    def compile_(fn, *specs, **jit_kwargs):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in specs]
        return jax.jit(fn, **jit_kwargs).lower(*args).compile().as_text()

    yield compile_
    jax.config.update('jax_enable_compilation_cache', was)


def _kv(hkv, dtype=BF):
    return ((P, PS, hkv, DH), dtype)


@pytest.mark.parametrize('hkv', [32, 8])
def test_flash_decode_contiguous(compile_tpu, hkv):
    txt = compile_tpu(
        lambda q, k, v, p: fd.flash_decode(q, k, v, p, scale=0.125,
                                           interpret=False),
        ((B, 1, 32, DH), BF), ((B, 4096, hkv, DH), BF),
        ((B, 4096, hkv, DH), BF), ((B,), I32))
    assert 'tpu_custom_call' in txt


@pytest.mark.parametrize('hkv', [32, 8])
def test_flash_decode_paged(compile_tpu, hkv):
    txt = compile_tpu(
        lambda q, k, v, p, bt: fd.flash_decode_paged(
            q, k, v, p, bt, scale=0.125, interpret=False),
        ((B, 1, hkv, DH), BF), _kv(hkv), _kv(hkv), ((B,), I32),
        ((B, W), I32))
    assert 'tpu_custom_call' in txt


@pytest.mark.parametrize('hkv', [32, 8])
def test_flash_decode_paged_q8(compile_tpu, hkv):
    txt = compile_tpu(
        lambda q, k, v, kq, vq, ks, vs, p, bt, hw: fd.flash_decode_paged_q8(
            q, k, v, kq, vq, ks, vs, p, bt, hw, scale=0.125,
            interpret=False),
        ((B, 1, hkv, DH), BF), _kv(hkv), _kv(hkv), _kv(hkv, I8),
        _kv(hkv, I8), ((P, hkv), F32), ((P, hkv), F32), ((B,), I32),
        ((B, W), I32), ((1,), I32))
    assert 'tpu_custom_call' in txt


@pytest.mark.parametrize('hkv', [32, 8])
def test_flash_chunk_paged(compile_tpu, hkv):
    txt = compile_tpu(
        lambda q, k, v, o, lim, bt: fd.flash_chunk_paged(
            q, k, v, o, lim, bt, scale=0.125, interpret=False),
        ((1, PS, hkv, DH), BF), _kv(hkv), _kv(hkv), ((1,), I32),
        ((1,), I32), ((1, W), I32))
    assert 'tpu_custom_call' in txt


def test_flash_decode_paged_mla(compile_tpu):
    txt = compile_tpu(
        lambda q, c, p, bt: fd.flash_decode_paged_mla(
            q, c, p, bt, r=R, scale=0.1, interpret=False),
        ((B, 1, HM, R + DR), BF), ((P, PS, R + DR), BF), ((B,), I32),
        ((B, W), I32))
    assert 'tpu_custom_call' in txt


def test_flash_decode_paged_mla_q8(compile_tpu):
    txt = compile_tpu(
        lambda q, c, cq, cs, p, bt, hw: fd.flash_decode_paged_mla_q8(
            q, c, cq, cs, p, bt, hw, r=R, scale=0.1, interpret=False),
        ((B, 1, HM, R + DR), BF), ((P, PS, R + DR), BF),
        ((P, PS, R + DR), I8), ((P, 1), F32), ((B,), I32), ((B, W), I32),
        ((1,), I32))
    assert 'tpu_custom_call' in txt


def test_flash_chunk_paged_mla(compile_tpu):
    txt = compile_tpu(
        lambda q, c, o, lim, bt: fd.flash_chunk_paged_mla(
            q, c, o, lim, bt, r=R, scale=0.1, interpret=False),
        ((1, PS, HM, R + DR), BF), ((P, PS, R + DR), BF), ((1,), I32),
        ((1,), I32), ((1, W), I32))
    assert 'tpu_custom_call' in txt


@pytest.mark.parametrize('k,n', [(2048, 5632), (5632, 2048)])
def test_int8_matmul(compile_tpu, k, n):
    txt = compile_tpu(
        lambda x, w: yoco_vmm.int8_matmul(x, w, interpret=False),
        ((128, k), I8), ((k, n), I8))
    assert 'tpu_custom_call' in txt


def test_yoco_vmm_int8(compile_tpu):
    txt = compile_tpu(
        lambda x, w, sx, sw: yoco_vmm.yoco_vmm_int8(x, w, sx, sw,
                                                    interpret=False),
        ((128, 2048), I8), ((2048, 5632), I8), ((128, 1), F32),
        ((1, 5632), F32))
    assert 'tpu_custom_call' in txt


def test_quantize_rows(compile_tpu):
    txt = compile_tpu(
        lambda x: quantize.quantize_rows(x, bm=128, interpret=False),
        ((128, 2048), BF))
    assert 'tpu_custom_call' in txt


@pytest.mark.parametrize('m', [4, 128])
@pytest.mark.parametrize('k,n', [(2048, 2048), (2048, 100352)])
def test_ops_int8_matmul_serving_shapes(compile_tpu, monkeypatch, m, k, n):
    """The padded wrapper the w8a8 serving path calls, at a decode batch
    (m = 4 slots) and a prefill slab (m = 128 prompt tokens), for the
    attention projection and the lm_head."""
    monkeypatch.setattr(ops, '_interpret', lambda: False)
    txt = compile_tpu(ops.int8_matmul, ((m, k), I8), ((k, n), I8))
    assert 'tpu_custom_call' in txt


@pytest.mark.parametrize('mode,kv_quant', [('bf16', False), ('bf16', True),
                                           ('w8a8', False)])
def test_stablelm_decode_step_full_width(compile_tpu, monkeypatch, mode,
                                         kv_quant):
    """The whole continuous decode step of stablelm-1.6b at its published
    widths (24 layers, 4 slots, 16-token pages, 160 positions), built the
    way ``serve_continuous`` builds it on a TPU: flash attention over the
    paged pool and, for w8a8, prequantized int8 weights through the Pallas
    matmul. The scanned layer body carries the attention kernel (plus the
    int8 matmuls under w8a8)."""
    from repro import configs
    from repro.core import yoco_linear
    from repro.core.yoco_linear import YocoConfig
    from repro.models import model as model_mod
    from repro.models.model import ModelRuntime
    from repro.runtime import serve_step as SS

    monkeypatch.setattr(ops, '_interpret', lambda: False)
    cfg = configs.get('stablelm-1.6b', smoke=False)
    slots, ps, max_blocks = 4, 16, 10

    def make_params(key):
        p = model_mod.init_params(key, cfg)
        return yoco_linear.quantize_tree(p) if mode == 'w8a8' else p

    params = jax.eval_shape(make_params, jax.random.key(0))
    cache = jax.eval_shape(lambda: model_mod.init_paged_cache_tree(
        cfg, slots, num_pages=1 + slots * max_blocks, page_size=ps,
        max_blocks=max_blocks, kv_dtype='int8' if kv_quant else None,
        hot_window=2))
    step = SS.make_decode_step(cfg, YocoConfig(mode=mode, use_pallas=True),
                               ModelRuntime(attn_impl='flash'))
    specs = [(a.shape, a.dtype) for a in jax.tree.leaves((params, cache))]
    treedef = jax.tree.structure((params, cache))

    def flat_step(tok, pos, *leaves):
        p, c = jax.tree.unflatten(treedef, leaves)
        return step(p, tok, pos, c)

    txt = compile_tpu(flat_step, ((slots,), I32), ((slots,), I32), *specs)
    assert txt.count('tpu_custom_call') >= (2 if mode == 'w8a8' else 1)


_HLO_OP = re.compile(r'^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\{[^}]*\} '
                     r'([\w-]+)\(')
_ALIAS = re.compile(r'\{(\d+)\}: \((\d+), \{\}')


def _assert_step_updates_pools_in_place(compile_tpu, monkeypatch, cfg, step,
                                        mode, kv_quant, *, slots, max_blocks,
                                        prompt):
    """Compile ``cfg``'s decode or prefill step for a described v5e the
    way ``serve_continuous`` runs it — 8-row pages, ``1 + slots *
    max_blocks`` of them, the pools' rows padded to whole lanes as on a
    TPU (``kv_cache.row_width``), the cache donated — and assert that no
    ``copy``, dynamic-slice or dynamic-update-slice fusion or fresh
    ``AllocateBuffer`` is shaped like a stacked pool leaf or like one
    layer's, or leads with the (layers, pages) dims of every stacked
    per-page leaf (pools and the int8 tier's scales), and that every pool
    comes back in its donated buffer."""
    from repro.core import yoco_linear
    from repro.core.yoco_linear import YocoConfig
    from repro.models import model as model_mod
    from repro.models.model import ModelRuntime
    from repro.runtime import kv_cache as kvc
    from repro.runtime import layouts
    from repro.runtime import serve_step as SS

    monkeypatch.setattr(ops, '_interpret', lambda: False)
    monkeypatch.setattr(kvc, '_lanes', lambda: kvc.LANES)
    ps = 8

    def make_params(key):
        p = model_mod.init_params(key, cfg)
        return yoco_linear.quantize_tree(p) if mode == 'w8a8' else p

    params = jax.eval_shape(make_params, jax.random.key(0))
    cache = jax.eval_shape(lambda: model_mod.init_paged_cache_tree(
        cfg, slots if step == 'decode' else 1,
        num_pages=1 + slots * max_blocks, page_size=ps,
        max_blocks=max_blocks, kv_dtype='int8' if kv_quant else None,
        hot_window=2))
    layer = cache['layers']
    assert layouts.get_layout(layer).name == ('paged_q8' if kv_quant
                                              else 'paged')
    pools = [k for k in ('k', 'v', 'kq', 'vq') if k in layer]
    assert all(layer[k].shape[-1] % kvc.LANES == 0 for k in pools)
    shapes = {s for k in pools
              for s in (layer[k].shape, layer[k].shape[1:])}
    per_page = layer['k'].shape[:2]                       # (L, P)
    yoco = YocoConfig(mode=mode, use_pallas=True)
    rt = ModelRuntime(attn_impl='flash')
    if step == 'decode':
        body = SS.make_decode_step(cfg, yoco, rt)
        lead = [((slots,), I32), ((slots,), I32)]        # tokens, positions
        n_out_lead = 2                                   # tokens, logits
    else:
        prefill = SS.make_prefill_step(cfg, yoco, rt)

        def body(p, toks, c, last_pos):
            return prefill(p, dict(inputs=toks), c, last_pos)
        lead = [((1, prompt), I32)]                       # padded prompt
        n_out_lead = 1                                    # logits
    p_leaves, c_leaves = jax.tree.leaves(params), jax.tree.leaves(cache)
    p0 = len(lead)
    c0 = p0 + len(p_leaves)

    def flat_step(*args):
        p = jax.tree.unflatten(jax.tree.structure(params), args[p0:c0])
        c = jax.tree.unflatten(jax.tree.structure(cache),
                               args[c0:c0 + len(c_leaves)])
        return body(p, *args[:p0], c, *args[c0 + len(c_leaves):])

    tail = [] if step == 'decode' else [((1,), I32)]      # last position
    txt = compile_tpu(
        flat_step, *lead, *[(a.shape, a.dtype) for a in p_leaves],
        *[(a.shape, a.dtype) for a in c_leaves], *tail,
        donate_argnums=tuple(range(c0, c0 + len(c_leaves))))

    copies = []
    for line in txt.splitlines():
        m = _HLO_OP.match(line)
        if m is None:
            continue
        name, dims, op = m.groups()
        shape = tuple(int(d) for d in dims.split(',') if d)
        if (shape in shapes or shape[:2] == per_page) and (
                op == 'copy' or (op == 'fusion' and 'dynamic' in name)
                or 'AllocateBuffer' in line):
            copies.append(line.strip()[:160])
    assert not copies, copies

    header = next(l for l in txt.splitlines() if l.startswith('HloModule'))
    aliased = {int(o): int(p) for o, p in _ALIAS.findall(
        header.split('input_output_alias=')[1].split('entry_computation')[0])}
    keys = sorted(layer)                   # flattening order of the dict
    for key in pools:
        i = keys.index(key)
        assert aliased.get(n_out_lead + i) == c0 + i, (key, aliased)


@pytest.mark.parametrize('step', ['decode', 'prefill'])
@pytest.mark.parametrize('mode,kv_quant', [('bf16', False), ('bf16', True),
                                           ('w8a8', False)])
def test_stablelm_step_updates_pools_in_place(compile_tpu, monkeypatch,
                                              step, mode, kv_quant):
    """The layer scan reads and writes the stacked paged pools in place:
    stablelm-1.6b at its published widths (head dim 64, stored 128 wide),
    4 lanes of 128 pages — 513 pages, a pool size at which the chip's
    default layout for unpadded 64-wide rows puts the page axis minor-most,
    so pools that lost their padding would be re-laid-out (copied)
    here."""
    from repro import configs
    _assert_step_updates_pools_in_place(
        compile_tpu, monkeypatch, configs.get('stablelm-1.6b', smoke=False),
        step, mode, kv_quant, slots=4, max_blocks=128, prompt=128)


@pytest.mark.parametrize('step', ['decode', 'prefill'])
def test_stablelm_12b_step_updates_pools_in_place(compile_tpu, monkeypatch,
                                                  step):
    """The same at stablelm-12b's widths as its cells serve it (8 KV heads
    of 160, stored 256 wide; w8a8 with the int8 KV tier), at the offline
    cell's pool: 36 lanes of 512 pages, 18433 pages. Two of the layers
    keep the compile short; the pools' layout does not depend on depth."""
    import dataclasses

    from repro import configs
    cfg = dataclasses.replace(configs.get('stablelm-12b', smoke=False),
                              n_layers=2)
    _assert_step_updates_pools_in_place(
        compile_tpu, monkeypatch, cfg, step, 'w8a8', True, slots=36,
        max_blocks=512, prompt=128)


def test_stablelm_tp4_decode_step_full_width(topo, compile_tpu, monkeypatch):
    """The ``--tp 4`` decode step of stablelm-1.6b over a mesh of the four
    described chips: the flash kernel runs inside ``shard_map`` on each
    chip's 8 KV heads, and the per-head outputs are all-gathered before
    the replicated ``wo``."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from repro import configs
    from repro.core.yoco_linear import YocoConfig
    from repro.models import model as model_mod
    from repro.runtime import serve_step as SS

    monkeypatch.setattr(ops, '_interpret', lambda: False)
    cfg = configs.get('stablelm-1.6b', smoke=False)
    mesh = Mesh(np.asarray(topo.devices[:4]), ('model',))
    slots, ps, max_blocks = 4, 16, 10
    params = jax.eval_shape(lambda: model_mod.init_params(jax.random.key(0),
                                                          cfg))
    cache = jax.eval_shape(lambda: model_mod.init_paged_cache_tree(
        cfg, slots, num_pages=1 + slots * max_blocks, page_size=ps,
        max_blocks=max_blocks))
    pspecs, cspecs = SS.serve_tp_specs(params, cache)

    def placed(tree, specs):
        return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, s)), tree, specs)

    vec = jax.ShapeDtypeStruct((slots,), I32,
                               sharding=NamedSharding(mesh, PartitionSpec()))
    step = SS.make_tp_decode_step(cfg, YocoConfig(mode='bf16', use_pallas=True),
                                  mesh, params, cache, attn_impl='flash')
    txt = jax.jit(step).lower(placed(params, pspecs), vec, vec,
                              placed(cache, cspecs)).compile().as_text()
    assert 'tpu_custom_call' in txt and 'all-gather' in txt


def _reader_kernel(metric):
    """The kernel pattern a benchmark reader matches in the trace."""
    import importlib.util
    from chipbench.harness import cell
    spec = importlib.util.spec_from_file_location(
        'reader', cell.reader_path(metric))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.KERNEL


@pytest.mark.parametrize('metric', ['decode_attn_roofline',
                                    'decode_attn_q8_roofline',
                                    'w8a8_matmul_roofline'])
def test_kernel_names_the_benchmark_reads(compile_tpu, metric):
    """Each roofline reader finds its kernel by the name of its custom
    call in the trace's op events (less the numeric suffix): the compiled
    program must carry exactly that name."""
    import re
    from chipbench.harness import trace as trace_mod
    hkv = 8
    if metric == 'decode_attn_roofline':
        txt = compile_tpu(
            lambda q, k, v, p, bt: fd.flash_decode_paged(
                q, k, v, p, bt, scale=0.125, interpret=False),
            ((B, 1, hkv, DH), BF), _kv(hkv), _kv(hkv), ((B,), I32),
            ((B, W), I32))
    elif metric == 'decode_attn_q8_roofline':
        txt = compile_tpu(
            lambda q, k, v, kq, vq, ks, vs, p, bt, hw:
            fd.flash_decode_paged_q8(q, k, v, kq, vq, ks, vs, p, bt, hw,
                                     scale=0.125, interpret=False),
            ((B, 1, hkv, DH), BF), _kv(hkv), _kv(hkv), _kv(hkv, I8),
            _kv(hkv, I8), ((P, hkv), F32), ((P, hkv), F32), ((B,), I32),
            ((B, W), I32), ((1,), I32))
    else:
        txt = compile_tpu(
            lambda x, w: yoco_vmm.int8_matmul(x, w, interpret=False),
            ((128, 2048), I8), ((2048, 5632), I8))
    calls = [trace_mod.op_label(line.strip().removeprefix('ROOT '))
             for line in txt.splitlines()
             if ' custom-call(' in line and 'tpu_custom_call' in line]
    rx = re.compile(_reader_kernel(metric))
    assert calls and all(rx.search(c) for c in calls), calls
