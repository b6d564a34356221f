"""Chip smoke test: serve stablelm-1.6b at its published widths on a TPU
through the compiled Pallas kernels, end to end.

    python chip_smoke.py            # phases (a)-(c), one chip
    python chip_smoke.py --tp 4     # phase (a) at --tp 4 vs --tp 1, 4 chips

Each phase runs ``repro.launch.serve.serve_continuous`` — the function
behind ``python -m repro.launch.serve --continuous --full`` — on 8
requests over 4 slots (prompts up to 128 tokens, up to 32 generated,
page size 16), with random weights from ``--seed``:

  (a) bf16 weights, flash-decode over the paged KV pool;
  (b) the same with ``--kv-quant --hot-window 2`` (the int8 tier is read);
  (c) ``--mode w8a8 --prequantize`` (int8 weights through
      ``ops.int8_matmul``).

Every phase checks: all requests finished, no ``degrade`` event, one
decode compilation, the compiled decode step calls Pallas kernels
(``tpu_custom_call``; phase (c) also its int8 matmuls), and the first
decode step's logits agree with the same step on the einsum oracle
within ``LOGIT_TOL`` (max |diff| over max |oracle logit|, live lanes).
``--tp N`` runs phase (a) at tensor-parallel degree N and at 1, and
requires identical greedy tokens.

One JSON line per phase, then ``{"ok": true, "device": {...}}`` as the
last line. Exits non-zero, printing no result, when JAX finds no TPU,
when a kernel fails to compile, or when any check fails.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                'src'))

ARCH = 'stablelm-1.6b'
RUN = dict(slots=4, n_requests=8, prompt_len=128, gen_len=32, page_size=16)
HOT_WINDOW = 2
# first-decode-step logit error vs the einsum oracle, max |diff| over max
# |oracle logit|. bf16: the oracle rounds K/V and probabilities to bf16,
# the kernel keeps f32 tiles (~1e-2 at full width on XLA-CPU with 2 and
# 6 layers). w8a8 adds per-token int8 activation codes that flip on that
# difference (~4e-2 there). A kernel that mixes up heads gives ~1.3.
LOGIT_TOL = dict(bf16=5e-2, w8a8=1.5e-1)


class SmokeFailure(RuntimeError):
    pass


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def decode_kernel_calls(cfg, mode: str, prequantize: bool,
                        kv_quant: bool) -> int:
    """Number of Pallas kernel calls (``tpu_custom_call``) in the compiled
    single-device decode step the phase serves with — the same builder,
    config and shapes as ``serve_continuous``, from abstract shapes (a
    TP shard runs the same kernels on its slice of the heads)."""
    import jax
    import jax.numpy as jnp
    from repro.core import yoco_linear
    from repro.core.yoco_linear import YocoConfig
    from repro.launch import serve
    from repro.models import model as model_mod
    from repro.models.model import ModelRuntime
    from repro.runtime import serve_step as SS

    def make_params(key):
        p = model_mod.init_params(key, cfg)
        return yoco_linear.quantize_tree(p) if prequantize else p

    slots, ps = RUN['slots'], RUN['page_size']
    max_blocks = -(-(RUN['prompt_len'] + RUN['gen_len']) // ps)
    params = jax.eval_shape(make_params, jax.random.key(0))
    cache = jax.eval_shape(lambda: model_mod.init_paged_cache_tree(
        cfg, slots, num_pages=1 + slots * max_blocks, page_size=ps,
        max_blocks=max_blocks, kv_dtype='int8' if kv_quant else None,
        hot_window=HOT_WINDOW))
    yoco = YocoConfig(mode=mode, use_pallas=serve._on_tpu())
    step = jax.jit(SS.make_decode_step(cfg, yoco,
                                       ModelRuntime(attn_impl='flash')),
                   donate_argnums=(3,))
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32)
    return step.lower(params, vec, vec, cache).compile().as_text() \
        .count('tpu_custom_call')


def run_phase(name: str, *, seed: int, tp: int = 1, mode: str = 'bf16',
              prequantize: bool = False, kv_quant: bool = False) -> dict:
    from repro import configs
    from repro.launch import serve

    cfg = configs.get(ARCH, smoke=False)
    kernels = decode_kernel_calls(cfg, mode, prequantize, kv_quant)
    t0 = time.perf_counter()
    out = serve.serve_continuous(
        ARCH, smoke=False, seed=seed, tp=tp, mode=mode,
        prequantize=prequantize, attn_impl='flash', kv_quant=kv_quant,
        hot_window=HOT_WINDOW, oracle_check=True, quiet=True, **RUN)
    wall = time.perf_counter() - t0
    line = dict(
        phase=name, arch=ARCH,
        widths=dict(n_layers=cfg.n_layers, d_model=cfg.d_model,
                    n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                    d_ff=cfg.d_ff, vocab_size=cfg.vocab_size),
        tp=tp, mode=mode, prequantize=prequantize, kv_quant=kv_quant,
        requests=out['requests'], completed=out['completed'],
        degrade=out['events'].get('degrade', 0),
        attn_impl_effective=out['attn_impl_effective'],
        decode_compilations=out['decode_compilations'],
        decode_kernel_calls=kernels,
        pages_quantized=out['pages_quantized'],
        oracle_logit_err=out['oracle_logit_err'], logit_tol=LOGIT_TOL[mode],
        steps=out['steps'], decode_tokens=out['decode_tokens'],
        cold_wall_s=wall)
    print(json.dumps(line), flush=True)
    _check(out['completed'] == out['requests'],
           f'{name}: {out["completed"]}/{out["requests"]} requests finished')
    _check(line['degrade'] == 0 and out['attn_impl_effective'] == 'flash',
           f'{name}: the stream degraded off the flash kernels')
    _check(out['decode_compilations'] == 1,
           f'{name}: {out["decode_compilations"]} decode compilations')
    # (c) also runs its int8 matmuls as kernels: more than the one
    # attention call of the scanned layer body
    _check(kernels >= (2 if mode == 'w8a8' else 1),
           f'{name}: {kernels} Pallas kernel calls in the decode step')
    _check(not kv_quant or out['pages_quantized'] > 0,
           f'{name}: no page reached the int8 tier')
    err = out['oracle_logit_err']
    _check(err is not None and err <= LOGIT_TOL[mode],
           f'{name}: logit error vs the einsum oracle {err} > '
           f'{LOGIT_TOL[mode]}')
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--tp', type=int, default=1,
                    help='run phase (a) at this tensor-parallel degree and '
                         'at 1, and compare greedy tokens (needs N chips)')
    ap.add_argument('--seed', type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != 'tpu':
        print(f'chip_smoke: no TPU (JAX sees {devices[0].platform})',
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    try:
        if args.tp > 1:
            sharded = run_phase(f'a_tp{args.tp}', seed=args.seed, tp=args.tp)
            single = run_phase('a_tp1', seed=args.seed)
            same = sharded['outputs'] == single['outputs']
            print(json.dumps(dict(phase=f'tp{args.tp}_vs_tp1',
                                  identical_tokens=same,
                                  requests=len(single['outputs']))),
                  flush=True)
            _check(same, f'--tp {args.tp} greedy tokens differ from --tp 1')
        else:
            run_phase('a_bf16_flash', seed=args.seed)
            run_phase('b_kv_quant', seed=args.seed, kv_quant=True)
            run_phase('c_w8a8_prequant', seed=args.seed, mode='w8a8',
                      prequantize=True)
    except SmokeFailure as e:
        print(f'chip_smoke: FAIL: {e}', file=sys.stderr)
        return 1
    print(json.dumps(dict(ok=True, device=dict(
        platform=devices[0].platform, kind=devices[0].device_kind,
        count=len(devices)))))
    return 0


if __name__ == '__main__':
    sys.exit(main())
