"""Run one cell of the benchmark once and print one JSON line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout, on a machine with the chips the cell
asks for. ``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1``
its per-layer metrics from a profiled run. The last stdout line is the
result; the numbers compared for ``correct`` are also the last lines on
stderr. Exits non-zero, with no result, when JAX finds no accelerator or
fewer chips than the cell asks for, or when the program is missing.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from chipbench.harness import cell
    try:
        result = cell.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), t_start=T_START)
    except (cell.NoDevice, FileNotFoundError) as e:
        print(f'chipbench: {e}', file=sys.stderr)
        return 2
    for k, v in result['compared'].items():
        print(f"compared {k}: {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
