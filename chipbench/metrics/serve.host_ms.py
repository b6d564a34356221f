"""Scheduler loop: the host's own time per loop step, from the program's
spans in the trace (``serve.step`` less the ``serve.wait`` nested in it)."""

from chipbench.harness import spans


def read(ctx):
    return spans.host_ms(spans.load(ctx))
