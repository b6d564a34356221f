"""Device: 1 - busy / window over the traced window, in percent."""

from chipbench.harness import readers


def read(ctx):
    return readers.idle_pct(ctx)
