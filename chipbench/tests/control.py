"""Readings for the correctness limit of a cell, on the chip.

    python3 chipbench/tests/control.py --workload <cell> --seeds 1,2,3 \
        --seconds <s>

Runs the cell once per seed in one process, each a short window at the
cell's own load, and prints per seed the program's reading (the widest
gap of a served token below the float32 reference's best) and the
control's (the float32 gap of the token the reference, computed in the
configuration's control precision, puts first, judged as the served
tokens are: ``control_correct`` has to read false). The limit lies
between the program's largest reading and the control's smallest.
"""

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seeds', required=True)
    ap.add_argument('--seconds', type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT)]
    from chipbench.harness import cell
    c = cell.load_cell(args.workload)
    control = c['config']['correct']['control']
    rows = []
    for seed in (int(s) for s in args.seeds.split(',')):
        r = cell.run_cell(args.workload, seed, args.seconds, False,
                          t_start=time.perf_counter(), cell=c,
                          control=control)
        row = dict(seed=seed, program=r['program_gap'],
                   control=r['compared']['max_logit_gap']['value'],
                   control_precision=control, control_correct=r['correct'])
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps(dict(
        workload=args.workload,
        program_max=max(r['program'] for r in rows),
        control_min=min(r['control'] for r in rows))), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
