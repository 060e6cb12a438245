"""SGD with bias-corrected EMA momentum in plain float32, and where the
program keeps its first update.

m <- μ m + (1 - μ) G, and the step is -lr m / (1 - μ^t) (``momentum`` 0:
-lr G).  The reference takes the keyword arguments the traffic file gives
the program's ``make_optimizer('sgd', ...)`` and refuses the ones it does
not model (weight decay, Nesterov, gradient clipping).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


class Reference:

    def __init__(self, lr: float, momentum: float, weight_decay: float = 0.0,
                 nesterov: bool = False, grad_clip=None):
        if weight_decay or nesterov or grad_clip:
            raise ValueError('the SGD reference models neither weight decay, '
                             'Nesterov momentum nor gradient clipping')

        @jax.jit
        def fold(state, g, stats, count):
            del stats, count
            m = momentum * state['m'] + (1.0 - momentum) * g if momentum \
                else g
            return dict(state, m=m), jnp.zeros((), jnp.float32)

        @jax.jit
        def apply(theta, state, factor, count):
            del factor
            corr = 1.0 - momentum ** count if momentum else 1.0
            step = lr * state['m'] / corr
            return ((theta.astype(jnp.float32) - step).astype(theta.dtype),
                    state)

        self.fold, self.apply = fold, apply

    @staticmethod
    def init(shape: tuple, has_stats: bool) -> dict:
        del has_stats
        return {'m': jnp.zeros(shape, jnp.float32)}

    def factor(self, partial) -> float:
        return 1.0

    @staticmethod
    def first_update(state: dict):
        return state['m']


def program_first_update(opt_state, params):
    """The program's momentum trace (the one parameter-shaped subtree of
    its optimizer state): (1 - μ) G after the first step."""
    from bench.harness import param_shaped
    found = param_shaped(opt_state, params)
    if len(found) != 1:
        raise RuntimeError(f'{len(found)} parameter-shaped subtrees in the '
                           'optimizer state, expected the momentum trace')
    return found[0]
