"""A configuration's model code is found by its ``model_type``
(``harness/arch.py``), and moving StableLM's code behind that lookup
changed no number: the counts at the configurations' published widths and
the reference's gaps at smoke widths are pinned to what the harness gave
when that code still lived in ``harness/counts.py`` and
``harness/reference.py`` (the expected values below were computed with
those functions, before the move)."""

import json
import pathlib
import types

import numpy as np
import pytest

from conftest import ROOT, SMOKE_MODEL

# (config file, lanes of its cells) -> pinned counts; keys name the call:
# int8_<lanes>: counts.int8_matmul(counts.matmul_calls(c, lanes, lanes)),
# attn_<lanes>_<ctx>: counts.decode_attn(c, serving, [ctx] * lanes),
# flops_<lanes>_<ctx>: counts.model_flops(c, [ctx] * lanes, []),
# *_prefill_3584: a prefill of 3584 tokens (LM head over its last row).
PINNED = {
    'stablelm-1.6b': {
        'int8_8': (23018340352.0, 1461829632.0),
        'attn_8_64': (100663296.0, 103809024.0),
        'flops_8_64': 23119003648.0,
        'attn_8_1536': (2415919104.0, 2419064832.0),
        'flops_8_1536': 25434259456.0,
        'attn_8_3584': (5637144576.0, 5640290304.0),
        'flops_8_3584': 28655484928.0,
        'int8_3': (8631877632.0, 1447340032.0),
        'attn_3_64': (37748736.0, 38928384.0),
        'flops_3_64': 8669626368.0,
        'attn_3_1536': (905969664.0, 907149312.0),
        'flops_3_1536': 9537847296.0,
        'attn_3_3584': (2113929216.0, 2115108864.0),
        'flops_3_3584': 10745806848.0,
        'int8_prefill_3584': (8839453736960.0, 10379208704.0),
        'flops_prefill_3584': 10102526443520.0,
    },
    'stablelm-12b-w8a8': {
        'int8_36': (137027911680.0, 1958627328.0),
        'attn_36_64': (235929600.0, 44305920.0),
        'flops_36_64': 137263841280.0,
        'attn_36_1536': (5662310400.0, 724723200.0),
        'flops_36_1536': 142690222080.0,
        'attn_36_3584': (13212057600.0, 1671390720.0),
        'flops_36_3584': 150239969280.0,
        'int8_64': (243605176320.0, 2001764352.0),
        'attn_64_64': (419430400.0, 78766080.0),
        'flops_64_64': 244024606720.0,
        'attn_64_1536': (10066329600.0, 1288396800.0),
        'flops_64_1536': 253671505920.0,
        'attn_64_3584': (23488102400.0, 2971361280.0),
        'flops_64_3584': 267093278720.0,
        'int8_prefill_3584': (9959983022080.0, 5968114688.0),
        'flops_prefill_3584': 10617833390080.0,
    },
}
LANES = {'stablelm-1.6b': (8, 3), 'stablelm-12b-w8a8': (36, 64)}

# correct.gaps on the smoke StableLM model, seed 123456789, for the sample
# drawn in test_gaps_unchanged_on_the_smoke_model (served, then fp8 control)
GAPS_SERVED = [
    1.142991065979004, 3.0317177772521973, 2.897606372833252,
    1.3886182308197021, 4.013853549957275, 4.123697757720947,
    3.9432196617126465, 3.2184276580810547, 3.690175771713257,
    0.48891258239746094, 3.093108654022217, 1.947082757949829,
    2.2442049980163574, 3.9162330627441406, 1.784719705581665,
    1.8436622619628906, 2.9330391883850098, 3.730583429336548,
    1.6505579948425293, 3.0915818214416504, 4.804887771606445,
    3.065150737762451, 3.91253399848938]
GAPS_CONTROL = [0.0] * 23
GAPS_CONTROL[2] = 0.01701498031616211
GAPS_CONTROL[11] = 0.03993654251098633
GAPS_CONTROL[17] = 0.1331472396850586
GAPS_CONTROL[19] = 0.11118268966674805


def _conf(name):
    return json.loads((ROOT / 'chipbench' / 'configs' /
                       f'{name}.json').read_text())


@pytest.mark.parametrize('name', sorted(PINNED))
def test_counts_pinned_at_published_widths(name):
    from chipbench.harness import counts
    conf = _conf(name)
    c = conf['model']
    serving = dict(conf['serving'], page_size=8)
    serving.setdefault('hot_window', 2)
    got = {}
    for n in LANES[name]:
        got[f'int8_{n}'] = counts.int8_matmul(counts.matmul_calls(c, n, n))
        for ctx in (64, 1536, 3584):
            got[f'attn_{n}_{ctx}'] = counts.decode_attn(c, serving, [ctx] * n)
            got[f'flops_{n}_{ctx}'] = counts.model_flops(c, [ctx] * n, [])
    got['int8_prefill_3584'] = counts.int8_matmul(
        counts.matmul_calls(c, 3584, 1))
    got['flops_prefill_3584'] = counts.model_flops(c, [], [3584])
    assert got == PINNED[name]


@pytest.mark.parametrize('name', sorted(PINNED))
def test_configurations_find_the_stablelm_module(name):
    from chipbench.harness import arch
    c = _conf(name)['model']
    assert c['model_type'] == 'stablelm'
    assert arch.of(c) is arch.module('stablelm')
    assert pathlib.Path(arch.of(c).__file__) == \
        ROOT / 'chipbench' / 'arch' / 'stablelm.py'


def test_unknown_model_type_names_the_missing_file():
    from chipbench.harness import arch, counts
    with pytest.raises(FileNotFoundError, match='chipbench/arch/nosuch.py'):
        arch.module('nosuch')
    with pytest.raises(FileNotFoundError, match='chipbench/arch/nosuch.py'):
        counts.model_flops(dict(model_type='nosuch'), [1], [])


TOY = '''
def model_flops(c, ctx, prefills):
    return c['flops_per_token'] * (len(ctx) + sum(prefills))
'''


def test_a_new_architecture_is_files_alone(tmp_path, monkeypatch):
    """A module written to another ``arch`` directory is found for its
    ``model_type``, and the ``mfu`` reader reads its counts through the
    harness as it stands."""
    from chipbench.harness import arch, readers
    (tmp_path / 'arch').mkdir()
    (tmp_path / 'arch' / 'toy_moe.py').write_text(TOY)
    monkeypatch.setattr(arch, 'ARCH_DIR', tmp_path / 'arch')
    c = dict(model_type='toy_moe', flops_per_token=1e9)
    assert arch.of(c).model_flops(c, [5, 7], [3]) == 5e9
    step = types.SimpleNamespace
    load = step(t_open=10.0, t_close=12.0,
                steps=[step(t=10.5, ctx=[5, 7], prefills=[]),
                       step(t=11.5, ctx=[6], prefills=[100]),
                       step(t=12.5, ctx=[9], prefills=[])])
    ctx = dict(load=load, config=c, serving=dict(mode='bf16'),
               peaks=dict(bf16_flops_per_s=1e12), tracer=None)
    # 2 + 1 decoded tokens and a 100-token prompt in a 2-s window
    assert readers.mfu_pct(ctx) == pytest.approx(100.0 * 103e9 / 2e12)


def test_gaps_unchanged_on_the_smoke_model():
    from chipbench.harness import correct
    model = dict(_conf('stablelm-1.6b')['model'], **SMOKE_MODEL)
    rng = np.random.default_rng(7)
    sample = [types.SimpleNamespace(
        prompt=rng.integers(0, 256, plen).tolist(), plen=plen,
        tokens=rng.integers(0, 256, n).tolist()) for plen, n in
        ((12, 9), (30, 14))]
    served, control = correct.gaps(123456789, model, sample, pad_to=48,
                                   control='fp8')
    np.testing.assert_array_equal(served, np.float32(GAPS_SERVED))
    np.testing.assert_array_equal(control, np.float32(GAPS_CONTROL))
