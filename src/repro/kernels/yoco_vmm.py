"""Pallas TPU kernel for the YOCO int8 VMM — the digital twin of an AiDAC core.

Dataflow (mirrors Fig. 4d phases, adapted to HBM->VMEM->MXU):

  * activations arrive ALREADY int8 (phase I/II, the one input conversion —
    see ``kernels/quantize.py``);
  * weight tiles are int8, resident in VMEM for the whole K loop (weights
    in situ, phase III);
  * the MXU computes int8 x int8 -> int32 per 128-aligned tile (phase IV,
    column charge-share accumulation);
  * an int32 accumulator lives in VMEM *scratch* across the K grid — partial
    sums never visit HBM and are never re-quantized (phase V + the paper's
    time-domain inter-macro accumulation);
  * the fp32 scale epilogue runs once, on the final K step (phase VI, the
    single TDC conversion). You Only Convert Once.

Grid: (M/bm, N/bn, K/bk), K innermost ("arbitrary"), M/N parallel. The x block
depends only on (i, k) so it is re-broadcast across the N tiles like the
paper's row drivers broadcast inputs across horizontal macros.

Block defaults are MXU-aligned (multiples of 128 in M/N; 256 in K for int8
sublane packing). The wrapper in ``ops.py`` pads arbitrary shapes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 256


def _yoco_vmm_kernel(xq_ref, wq_ref, sx_ref, sw_ref, out_ref, acc_ref, *,
                     k_steps: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # MXU int8 x int8 -> int32; never rounded mid-reduction (YOCO property).
    acc_ref[...] += jax.lax.dot_general(
        xq_ref[...], wq_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == k_steps - 1)
    def _epilogue():
        # The single output conversion (TDC): int32 -> fp32 with per-token x
        # per-out-channel scales, fused — no extra HBM round-trip.
        out_ref[...] = (acc_ref[...].astype(jnp.float32)
                        * sx_ref[...] * sw_ref[...])


@functools.partial(jax.jit, static_argnames=('bm', 'bn', 'bk', 'interpret'))
def yoco_vmm_int8(xq: jnp.ndarray, wq: jnp.ndarray, sx: jnp.ndarray,
                  sw: jnp.ndarray, *, bm: int = DEFAULT_BM,
                  bn: int = DEFAULT_BN, bk: int = DEFAULT_BK,
                  interpret: bool = False) -> jnp.ndarray:
    """xq: (M, K) int8; wq: (K, N) int8; sx: (M, 1) f32; sw: (1, N) f32.
    Returns (M, N) f32 = (xq @ wq) * sx * sw. Shapes must be multiples of
    the block sizes (pad in the wrapper)."""
    m, k = xq.shape
    k2, n = wq.shape
    assert k == k2 and m % bm == 0 and n % bn == 0 and k % bk == 0, \
        (xq.shape, wq.shape, bm, bn, bk)
    k_steps = k // bk
    grid = (m // bm, n // bn, k_steps)
    return pl.pallas_call(
        functools.partial(_yoco_vmm_kernel, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),   # activations
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),   # weights in situ
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),     # per-token scale
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),     # per-chan scale
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],       # the "time domain"
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary'),
        ),
        interpret=interpret,
    )(xq, wq, sx, sw)


def _int8_matmul_kernel(xq_ref, wq_ref, out_ref, acc_ref, *, k_steps: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        xq_ref[...], wq_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == k_steps - 1)
    def _flush():
        out_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=('bm', 'bn', 'bk', 'interpret'))
def int8_matmul(xq: jnp.ndarray, wq: jnp.ndarray, *, bm: int = DEFAULT_BM,
                bn: int = DEFAULT_BN, bk: int = DEFAULT_BK,
                interpret: bool = False) -> jnp.ndarray:
    """Raw int8 x int8 -> int32 tiled matmul (no epilogue); used when the
    caller owns the scales (pre-quantized serving path)."""
    m, k = xq.shape
    _, n = wq.shape
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    k_steps = k // bk
    return pl.pallas_call(
        functools.partial(_int8_matmul_kernel, k_steps=k_steps),
        grid=(m // bm, n // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary'),
        ),
        interpret=interpret,
    )(xq, wq)
