"""Paged flash-decode kernel, bf16 pages: live K/V bytes and operations
over the traced steps, against the kernel's summed device time."""

from chipbench.harness import readers

KERNEL = r'^flash_decode_gqa_paged$'


def read(ctx):
    f, b = readers.decode_attn_work(ctx)
    return readers.roofline_pct(ctx, KERNEL, f, b, 'bf16_flops_per_s')
