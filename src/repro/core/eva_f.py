"""Eva-f (paper §4.1): vectorized FOOF — input-side-only rank-one
preconditioning + hyper-parameter-free KL normalization.

Bucketed like ``eva``: one ``precondition_tree`` call per (shape, dtype)
bucket, bucket-level KV EMA, distributed psum hook.  KV-snapshot refresh is
scheduled through ``repro.schedule`` (same knob as the baselines).

``eva_f(fused=True)`` runs the preconditioner as one ``eva_f_fused``
launch per bucket, folding the ⟨p,g⟩ inner product the KL normalizer
needs into the kernel epilogue; the normalize + EMA tail itself stays a
single jnp pass (its global scale depends on every bucket, so it cannot
live inside a per-bucket launch — see ``kernels/fused.py``)."""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

from repro.core import kv as kvlib
from repro.core import precondition as pre
from repro.core.clipping import finish_normalized_ema, kl_normalize
from repro.core.eva import _kv_init, _kv_step, _zeros_like_spec
from repro.core.transform import (Extras, GradientTransformation, chain,
                                  add_decayed_weights, ema_trace,
                                  scale_by_schedule, tree_vdot)
from repro.schedule import policy as schedpol


class EvaFState(NamedTuple):
    running: kvlib.RunningStats
    cached: Any
    sched: schedpol.SchedState
    # pipeline='onestep': {'stats': PipelineState}; None in sync mode
    pipe: Any = None
    # fused path only: the f32 EMA momentum buffer (else in ema_trace state)
    trace: Any = None


_FIELDS = ('a_mean',)


def eva_f_preconditioner(gamma: float = 0.03, kv_decay: float = 0.95,
                         interval: int = 1,
                         policy: Optional[schedpol.RefreshPolicy] = None
                         ) -> GradientTransformation:

    def init(params, extras: Extras | None = None):
        return EvaFState(**_kv_init(params, extras, _FIELDS, policy,
                                    interval))

    def update(updates, state: EvaFState, params=None, extras: Extras | None = None):
        del params
        flat, plan, used, parts = _kv_step(
            state, updates, extras, fields=_FIELDS, site='stats/eva_f',
            policy=policy, interval=interval, kv_decay=kv_decay)
        out = pre.precondition_tree(flat, used, 'eva_f', gamma, plan=plan)
        return kvlib.unflatten_params(out), EvaFState(**parts)

    return GradientTransformation(init, update)


def eva_f_fused_update(gamma: float = 0.03, kv_decay: float = 0.95,
                       momentum: float = 0.9, fold_kl: bool = True,
                       interval: int = 1,
                       policy: Optional[schedpol.RefreshPolicy] = None
                       ) -> GradientTransformation:
    """Preconditioner + KL normalize + EMA momentum as ONE transform.

    The kernel emits P and the per-bucket ⟨p,g⟩ partials in a single
    launch; the tail is the shared ``finish_normalized_ema``.  Momentum
    cannot fold into the kernel here (normalization precedes the EMA and
    its scale is global), so ``fold_momentum`` stays off — the win is the
    merged launch and the folded inner product.  ``fold_kl=False`` (weight
    decay upstream) recomputes ⟨p, raw_grads⟩ instead of trusting the
    kernel partials.
    """

    def init(params, extras: Extras | None = None):
        return EvaFState(**_kv_init(params, extras, _FIELDS, policy,
                                    interval),
                         trace=_zeros_like_spec(params))

    def update(updates, state: EvaFState, params=None, extras: Extras | None = None):
        del params
        flat, plan, used, parts = _kv_step(
            state, updates, extras, fields=_FIELDS, site='stats/eva_f',
            policy=policy, interval=interval, kv_decay=kv_decay)
        out_flat, partials = pre.precondition_tree_fused(
            flat, used, 'eva_f', gamma, plan=plan, fold_momentum=False)
        p = kvlib.unflatten_params(out_flat)
        if fold_kl:
            pg = sum(partials[k][0] for k in sorted(partials))
        else:
            pg = tree_vdot(p, extras.raw_grads)
        out, stored = finish_normalized_ema(p, pg, state.trace, momentum,
                                            extras.step)
        return out, EvaFState(**parts, trace=stored)

    return GradientTransformation(init, update)


def eva_f(lr=0.1, gamma: float = 0.03, kv_decay: float = 0.95,
          momentum: float = 0.9, weight_decay: float = 0.0,
          interval: int = 1,
          policy: Optional[schedpol.RefreshPolicy] = None,
          fused: bool = False) -> GradientTransformation:
    parts = []
    if weight_decay:
        parts.append(add_decayed_weights(weight_decay))
    if fused:
        parts.append(eva_f_fused_update(
            gamma, kv_decay, momentum, fold_kl=(weight_decay == 0.0),
            interval=interval, policy=policy))
    else:
        parts.append(eva_f_preconditioner(gamma, kv_decay,
                                          interval=interval, policy=policy))
        parts.append(kl_normalize())
        parts.append(ema_trace(momentum))
    parts.append(scale_by_schedule(lr if callable(lr) else (lambda _: lr)))
    return chain(*parts)


CAPTURE = kvlib.EVA_F_CAPTURE
