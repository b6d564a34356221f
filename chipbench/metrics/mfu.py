"""Model FLOPs of the window's prompts and generated tokens over the
window times the chip's peak at the configuration's precision."""

from chipbench.harness import readers


def read(ctx):
    return readers.mfu_pct(ctx)
