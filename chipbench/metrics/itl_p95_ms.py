"""95th percentile of every gap between consecutive delivered tokens of
the requests due in the window (host clock)."""

from chipbench.harness import readers


def read(ctx):
    v = readers.p95(readers.itl_s(ctx))
    return None if v is None else v * 1e3
