"""Tokens delivered inside the window over the window (host clock)."""

from chipbench.harness import readers


def read(ctx):
    return readers.output_tok_s(ctx)
