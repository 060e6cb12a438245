"""The comparison that decides ``correct``.

Four numbers are compared with the plain reference, over the checked
first steps of the timed path:

* ``loss_gap``: the largest relative gap between the program's loss and
  the reference's at each checked step;
* ``update1_gap``: per leaf, the gap between the norms of the first update
  as the optimizer's state keeps it after step 1 (Eva's and SGD's momentum
  trace, read by ``bench/optimizers/<name>.py``);
* ``change_gap``: per leaf, the gap between the norms of the parameters'
  change over the checked steps, as the stored parameters keep it;
* ``unmoved_leaves``: the leaves whose first update or change the program
  leaves at exactly zero where the reference's is not zero -- a leaf whose
  update is lost, however small the leaf (limit 0).

A leaf's gap is |program norm - reference norm| over the larger of the
reference's norm of that leaf and of the median leaf, since some leaves'
updates are all but zero, and the number is the worst leaf.  Leaves whose
reference gradient is under a thousandth of the median leaf's move by
round-off alone (a key bias under softmax); they are left out of all the
per-leaf numbers by that rule.
"""
from __future__ import annotations

import math
import statistics

NOUGHT = 1e-3


def _gaps(prog: dict, ref: dict, keep) -> dict:
    med = statistics.median(ref.values())
    return {p: (abs(prog[p] - ref[p]) / max(ref[p], med, 1e-30)
                if p in prog and math.isfinite(prog[p]) else math.inf)
            for p in keep}


def _unmoved(prog: dict, ref: dict, keep) -> set:
    return {p for p in keep if ref[p] > 0 and prog.get(p, 1.0) == 0}


def _kept(ref: dict) -> list:
    g_med = statistics.median(ref['grad1'].values())
    return sorted(p for p, g in ref['grad1'].items() if g >= NOUGHT * g_med)


def readings(prog: dict, ref: dict) -> dict:
    """``prog``/``ref``: {'losses': [...], 'update1': {leaf: norm},
    'change': {leaf: norm}}; ``ref`` also has 'grad1'."""
    keep = _kept(ref)
    if len(prog['losses']) != len(ref['losses']):
        loss_gap = math.inf
    else:
        loss_gap = max((abs(a - b) / abs(b) if math.isfinite(a) else math.inf)
                       for a, b in zip(prog['losses'], ref['losses']))
    return {'loss_gap': loss_gap,
            'update1_gap': max(_gaps(prog['update1'], ref['update1'],
                                     keep).values()),
            'change_gap': max(_gaps(prog['change'], ref['change'],
                                    keep).values()),
            'unmoved_leaves': len(_unmoved(prog['update1'], ref['update1'],
                                           keep)
                                  | _unmoved(prog['change'], ref['change'],
                                             keep))}


def worst_leaves(prog: dict, ref: dict, n: int = 3) -> dict:
    """For the log: the ``n`` leaves with the widest gaps of each per-leaf
    number, and the leaves the rule on the gradient leaves out."""
    keep = _kept(ref)
    out = {}
    for key in ('update1', 'change'):
        gaps = _gaps(prog[key], ref[key], keep)
        out[key] = sorted(gaps.items(), key=lambda kv: -kv[1])[:n]
    out['left_out'] = sorted(set(ref['grad1']) - set(keep))
    return out


def checks(values: dict, limits: dict) -> dict:
    return {k: {'value': values[k], 'limit': limits[k]} for k in limits}


def passed(checks_: dict) -> bool:
    return all(math.isfinite(c['value']) and c['value'] <= c['limit']
               for c in checks_.values())
