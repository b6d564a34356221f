"""The comparison that decides ``correct``.

For each sampled request the reference runs once over its prompt and the
tokens the program served (teacher-forced, causal, so padding after the
end changes nothing before it). At every served position it reads the
gap by which the served token's reference logit lies below the
reference's best; the run's number is the widest gap. Greedy decoding
picks the program's own best, so a sound program's gap is rounding where
two logits nearly tie, and a wrong token or a wrong state shows as a gap
of the logits' own spread.

``control`` runs the same reference in a lower precision beside the
float32 one and reads, instead, the float32 gap of the token the lower
precision puts first.
"""

from __future__ import annotations

from typing import Optional

import numpy as np


def gaps(seed: int, model: dict, sample, *, pad_to: int,
         control: Optional[str] = None):
    """Per served token: (f32 gap of the served token, f32 gap of the
    control's first choice or None), one sequence at a time at the fixed
    length ``pad_to``."""
    from chipbench.harness import arch
    if not sample:
        return np.zeros(0), None
    ref = arch.of(model)
    served, ctrl = [], []
    for r in sample:
        seq = np.zeros((1, pad_to), np.int32)
        body = np.concatenate([np.asarray(r.prompt, np.int32),
                               np.asarray(r.tokens[:-1], np.int32)])
        seq[0, :len(body)] = body
        # served token k (0-based) was chosen at position plen - 1 + k
        pos = r.plen - 1 + np.arange(len(r.tokens))
        h = ref.hidden(seed, model, seq)
        hc = ref.hidden(seed, model, seq, control) if control else None
        g, gc = ref.head_gaps(seed, model, h, pos,
                              np.asarray(r.tokens, np.int32), hc, control)
        served.append(g)
        ctrl.append(gc)
    return (np.concatenate(served),
            np.concatenate(ctrl) if control else None)
