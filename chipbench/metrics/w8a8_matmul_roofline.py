"""Pallas int8 matmul: int8 operations and bytes of every call over the
traced steps at the algorithm's M, against the kernel's summed device time."""

from chipbench.harness import readers

KERNEL = r'^int8_matmul$'


def read(ctx):
    f, b = readers.int8_matmul_work(ctx)
    return readers.roofline_pct(ctx, KERNEL, f, b, 'int8_ops_per_s')
