"""Paged flash-decode kernel with the int8 tier: live K/V bytes (cold
pages int8 with their scales, the hot window bf16) and operations over the
traced steps, against the kernel's summed device time."""

from chipbench.harness import readers

KERNEL = r'^flash_decode_gqa_paged_q8$'


def read(ctx):
    f, b = readers.decode_attn_work(ctx)
    return readers.roofline_pct(ctx, KERNEL, f, b, 'bf16_flops_per_s')
