"""The control comes out not correct: the reference computed in the
precision below the configuration's (fp8 for bf16, int4 for int8) stands
in for the served tokens, and its picks, judged as the served tokens are,
lie over the limit, where the program's lie under it. At smoke widths on the CPU; the chip readings at
the cells' own sizes are in PERF.md (``control.py``)."""

import time

import pytest

from conftest import smoke

CELLS = ['stablelm-1.6b.chat', 'stablelm-12b-w8a8.offline-long']


@pytest.mark.parametrize('name', CELLS)
def test_control_reads_over_the_limit(load_cell, name):
    from chipbench.harness import cell as C
    c = smoke(load_cell(name))
    r = C.run_cell(name, 2 ** 32 + 11, 2.0, False,
                   t_start=time.perf_counter(), cell=c, require_chip=False,
                   control=c['config']['correct']['control'])
    gap = r['compared']['max_logit_gap']
    assert r['correct'] is False
    assert r['program_gap'] <= gap['limit'] < gap['value']
