"""The benchmark's own tests: run by path, from the checkout root,
``PYTHONPATH=src:. python -m pytest chipbench/tests`` (on the CPU, small
shapes). ``smoke_cell`` is a cell of the benchmark at widths a CPU test
can hold."""

import copy
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / 'src'):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

SMOKE_MODEL = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
                   num_key_value_heads=2, intermediate_size=160,
                   vocab_size=256)


def smoke(cell: dict, *, prompt_max=48, output=(8, 16), limit=None) -> dict:
    """The cell at smoke widths and short lengths, with its own name so
    the registry entry does not clash with the full-width one."""
    cell = copy.deepcopy(cell)
    cell['config']['name'] = cell['config']['name'] + '.smoke'
    cell['config']['model'].update(SMOKE_MODEL)
    mix = cell['mix']
    mix['prompt_len'].update(min=min(mix['prompt_len']['min'], 8),
                             max=prompt_max)
    if 'median' in mix['prompt_len']:
        mix['prompt_len']['median'] = prompt_max // 2
    mix['output_len'].update(min=output[0], max=output[1])
    if 'median' in mix['output_len']:
        mix['output_len']['median'] = (output[0] + output[1]) // 2
    cell['params'] = dict(cell['params'], slots=4)
    if 'rate_per_s' in cell['params']:
        cell['params']['rate_per_s'] = 20.0
    if limit is not None:
        cell['config']['correct']['max_logit_gap'] = limit
    return cell


@pytest.fixture
def load_cell():
    from chipbench.harness import cell
    return cell.load_cell
