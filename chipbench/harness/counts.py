"""Operations and bytes the algorithm needs, from shapes alone.

Bytes count the precision the configuration states, not what the program
happens to read: bf16 weights and KV for a bf16 configuration, int8
weights and int8 cold KV pages (with their f32 per-page, per-head scales)
for a w8a8 one. A program that reads more (f32 weights, padded rows, dead
tiles) therefore shows a lower share, and no share can pass 100% unless
the time leaves out part of the work.

``c`` is a configuration file's dict; ``serving`` its deployment part.
"""

from __future__ import annotations

import json
import pathlib
from typing import Iterable, List, Tuple

PEAKS = pathlib.Path(__file__).with_name('peaks.json')


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; unknown kinds are an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table['devices']:
        raise KeyError(f'no published peaks for device kind {device_kind!r}; '
                       f'known: {sorted(table["devices"])}')
    return table['devices'][device_kind]


def _dims(c: dict):
    d = c['hidden_size']
    h = c['num_attention_heads']
    return d, h, c['num_key_value_heads'], d // h, c['intermediate_size'], \
        c['vocab_size'], c['num_hidden_layers']


def layer_matmuls(c: dict) -> List[Tuple[str, int, int]]:
    """(name, K, N) of every projection one token passes in one layer."""
    d, h, hkv, dh, ff, _, _ = _dims(c)
    return [('wq', d, h * dh), ('wk', d, hkv * dh), ('wv', d, hkv * dh),
            ('wo', h * dh, d), ('w_gate', d, ff), ('w_up', d, ff),
            ('w_down', ff, d)]


def matmul_calls(c: dict, m: int, head_rows: int) -> List[Tuple[int, int, int]]:
    """(M, K, N) of every matmul one program call makes over ``m`` token
    rows: each layer's projections, then the LM head over ``head_rows``
    rows (every row when decoding, the last prompt row in a prefill)."""
    _, _, _, _, _, v, L = _dims(c)
    calls = [(m, k, n) for _ in range(L) for _, k, n in layer_matmuls(c)]
    calls.append((head_rows, c['hidden_size'], v))
    return calls


def int8_matmul(calls: Iterable[Tuple[int, int, int]]) -> Tuple[float, float]:
    """int8 x int8 -> int32 matmuls: (ops, bytes) over ``calls``; bytes
    are the int8 activations and weights read and the int32 result
    written."""
    ops = byt = 0.0
    for m, k, n in calls:
        ops += 2.0 * m * k * n
        byt += m * k + k * n + 4.0 * m * n
    return ops, byt


def decode_attn(c: dict, serving: dict, ctx: Iterable[int]) -> Tuple[float, float]:
    """(flops, bytes) of one decode step's attention over the live
    context of each lane, summed over layers. A lane at context ``n``
    attends positions 0..n-1; with the int8 tier the pages older than the
    hot window are read as int8 plus one f32 scale per page and head for
    K and for V, the rest as bf16. Queries in and outputs out are f32."""
    d, h, hkv, dh, _, _, L = _dims(c)
    ps = int(serving['page_size'])
    hw = int(serving['hot_window']) if serving.get('kv_quant') else None
    flops = byt = 0.0
    for n in ctx:
        flops += 4.0 * h * dh * n
        if hw is None:
            byt += n * hkv * dh * 2 * 2
        else:
            last = (n - 1) // ps                 # block being written
            cold_pages = max(0, last - hw + 1)
            cold = min(n, cold_pages * ps)
            byt += cold * hkv * dh * 2 * 1 + cold_pages * hkv * 2 * 4
            byt += (n - cold) * hkv * dh * 2 * 2
        byt += 2 * h * dh * 4
    return flops * L, byt * L


def model_flops(c: dict, ctx: Iterable[int], prefills: Iterable[int]) -> float:
    """Model FLOPs of a set of decode lanes (live contexts) and prompt
    prefills (actual lengths): 2 per weight per token through the
    projections, the LM head once per decoded token and once per prompt,
    and causal attention over the live context."""
    d, h, hkv, dh, _, v, L = _dims(c)
    per_tok = 2.0 * sum(k * n for _, k, n in layer_matmuls(c)) * L
    head = 2.0 * d * v
    att = 4.0 * h * dh * L
    total = 0.0
    for n in ctx:
        total += per_tok + head + att * n
    for p in prefills:
        total += per_tok * p + head + att * p * (p + 1) / 2.0
    return total


def peak_rate(serving: dict, pk: dict) -> float:
    """The chip's peak for the configuration's stated precision."""
    return pk['int8_ops_per_s'] if serving['mode'] == 'w8a8' \
        else pk['bf16_flops_per_s']
