"""Finds a configuration's model code by its ``model_type``.

``model['model_type']`` names ``chipbench/arch/<model_type>.py``, as a
metric's name names its reader. That module is the only contract between
the harness and an architecture, and holds everything the harness knows of
one. It defines:

- ``arch_config(conf) -> ArchConfig``: the program's configuration for a
  configuration file (``conf``, the whole file); the harness registers it
  under ``conf['name']`` so ``serve_continuous`` can look it up;
- the plain float32 reference (``correct.gaps`` calls it):
  ``hidden(seed, model, tokens, control=None)``, (1, S) int tokens to the
  (S, d) hidden states before the final norm, and
  ``head_gaps(seed, model, h, pos, toks, hc=None, control=None)``, the
  gaps of the served tokens ``toks`` at the rows ``pos`` below each row's
  best logit and those of the control's first choices (rows of ``hc``),
  as ``(served, control)`` arrays;
- the counts (``counts.py`` calls them): ``matmul_calls(model, m,
  head_rows)``, the (M, K, N) of every matmul of one program call over
  ``m`` token rows; ``decode_attn(model, serving, ctx)``, the (flops,
  bytes) of one decode step's attention over the lanes' live contexts;
  ``model_flops(model, ctx, prefills)``, the model FLOPs of decode lanes
  and prompt prefills.

``model`` is the file's ``model`` part, ``serving`` its deployment with the
page size and hot window filled in.
"""

from __future__ import annotations

import functools
import importlib.util
import pathlib

ARCH_DIR = pathlib.Path(__file__).resolve().parents[1] / 'arch'


@functools.cache
def _load(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        'chipbench_arch_' + path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def module(model_type: str):
    """The module of ``model_type``, imported once per process (its jitted
    functions keep their compiled programs)."""
    path = ARCH_DIR / f'{model_type}.py'
    if '/' in model_type or not path.is_file():
        raise FileNotFoundError(
            f'no architecture module {path} for the model_type '
            f'{model_type!r}')
    return _load(path)


def of(model: dict):
    """The module of a configuration's ``model`` part."""
    return module(model['model_type'])
