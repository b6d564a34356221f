"""Operations and bytes the algorithm needs, from shapes alone.

Bytes count the precision the configuration states, not what the program
happens to read: bf16 weights and KV for a bf16 configuration, int8
weights and int8 cold KV pages (with their f32 per-page, per-head scales)
for a w8a8 one. A program that reads more (f32 weights, padded rows, dead
tiles) therefore shows a lower share, and no share can pass 100% unless
the time leaves out part of the work.

``c`` is a configuration file's ``model`` part, ``serving`` its deployment
part; the shapes are the architecture's (``chipbench/arch/<model_type>.py``,
found by ``harness/arch.py``), the peaks and the int8 matmul's work are
here.
"""

from __future__ import annotations

import json
import pathlib
from typing import Iterable, List, Tuple

from chipbench.harness import arch

PEAKS = pathlib.Path(__file__).with_name('peaks.json')


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; unknown kinds are an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table['devices']:
        raise KeyError(f'no published peaks for device kind {device_kind!r}; '
                       f'known: {sorted(table["devices"])}')
    return table['devices'][device_kind]


def matmul_calls(c: dict, m: int, head_rows: int) -> List[Tuple[int, int, int]]:
    """(M, K, N) of every matmul of one program call over ``m`` token rows."""
    return arch.of(c).matmul_calls(c, m, head_rows)


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
    """(flops, bytes) of one decode step's attention over the lanes'
    live contexts."""
    return arch.of(c).decode_attn(c, serving, ctx)


def model_flops(c: dict, ctx: Iterable[int], prefills: Iterable[int]) -> float:
    """Model FLOPs of decode lanes (live contexts) and prompt prefills."""
    return arch.of(c).model_flops(c, ctx, prefills)


def peak_rate(serving: dict, pk: dict) -> float:
    """The chip's peak for the configuration's stated precision."""
    return pk['int8_ops_per_s'] if serving['mode'] == 'w8a8' \
        else pk['bf16_flops_per_s']
