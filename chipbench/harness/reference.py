"""What every architecture's plain float32 reference shares: the weight
draw, the control's lower-precision matmul, the gaps of served tokens
below the best logit, and the LM head's row blocks. Each architecture's
own forward is ``chipbench/arch/<model_type>.py`` (``harness/arch.py``).

A reference imports nothing of the program under test and takes no array
the program made: it re-draws the weights from the seed by the scheme the
configuration file states.

``control`` names a lower precision for every projection's operands
(weights per output channel, activations per token): ``fp8`` (e4m3) or
``int4`` (symmetric, levels -7..7). The control reading of a cell is the
gap, in the float32 reference's logits, of the token the control puts
first.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
ROW_BLOCK = 512


def _normal(key, shape, fan_in: Optional[int] = None, std: float = 0.0):
    x = jax.random.normal(key, shape, jnp.float32)
    if fan_in is not None:
        return x * (1.0 / jnp.sqrt(fan_in))
    return x * std


# ----------------------------------------------------------------------------
# lower-precision operands for the control
# ----------------------------------------------------------------------------
def _lowp(x, axis: int, control: Optional[str]):
    """Round ``x`` to the control's precision, scaled per slice along
    every axis but ``axis`` (absmax to the format's largest value)."""
    if control is None:
        return x
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    if control == 'fp8':
        s = jnp.maximum(amax, 1e-30) / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    if control == 'int4':
        s = jnp.maximum(amax, 1e-30) / 7.0
        return jnp.clip(jnp.round(x / s), -7, 7) * s
    raise ValueError(f'unknown control precision {control!r}')


def _mm(x, w, control):
    """(..., K) @ (K, N) in float32, operands at the control's precision
    (activations per token, weights per output channel)."""
    return jnp.matmul(_lowp(x, -1, control), _lowp(w, 0, control),
                      precision=HI)




# ----------------------------------------------------------------------------
# gaps below the best logit
# ----------------------------------------------------------------------------
def logit_gaps(lg, toks, lg_control=None):
    """For float32 logit rows ``lg``: the gap by which ``toks`` lie below
    each row's best, and that of the token ``lg_control`` (the control
    precision's logits of the same rows) puts first, or the served gaps
    again without a control."""
    best = jnp.max(lg, axis=-1)
    served = best - jnp.take_along_axis(lg, toks[:, None], axis=-1)[:, 0]
    if lg_control is None:
        return served, served
    pick = jnp.argmax(lg_control, axis=-1)
    return served, best - jnp.take_along_axis(lg, pick[:, None],
                                              axis=-1)[:, 0]


def row_blocks(head, h, pos: np.ndarray, toks: np.ndarray, hc=None):
    """``head(h, idx, toks, hc) -> (served, control)`` gaps over the rows
    ``pos`` of one sequence's hidden states, in blocks of ``ROW_BLOCK``
    rows (the last block padded by repeating its last row, and cut back),
    so every call has one shape."""
    n = len(pos)
    pad = -n % ROW_BLOCK
    pos = np.concatenate([pos, np.full(pad, pos[-1])]).astype(np.int32)
    toks = np.concatenate([toks, np.full(pad, toks[-1])]).astype(np.int32)
    out_s, out_c = [], []
    with jax.default_matmul_precision('highest'):
        for lo in range(0, len(pos), ROW_BLOCK):
            sl = slice(lo, lo + ROW_BLOCK)
            g, gc = head(h, jnp.asarray(pos[sl]), jnp.asarray(toks[sl]),
                         h if hc is None else hc)
            out_s.append(np.asarray(g))
            out_c.append(np.asarray(gc))
    return np.concatenate(out_s)[:n], np.concatenate(out_c)[:n]
