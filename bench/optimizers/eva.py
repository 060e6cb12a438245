"""Eva's update in plain float32, and where the program keeps its first update.

Eva (paper Eq. 13-16, as the program's composed chain orders it): for each
preconditioned weight W (d_in, d_out) the running means ā (layer input) and
b̄ (sum over tokens of the loss cotangent of the layer output) are EMA'd
with bias correction, P = (G - (āᵀGb̄)/(γ + |ā|²|b̄|²) ā b̄ᵀ)/γ; other leaves
take P = G.  Then m <- μ m + P, ν = min(1, sqrt(κ / (lr² max(<m, G>, 0))))
over all leaves, the stored trace is ν m and the step is -lr ν m.

The reference takes the keyword arguments the traffic file gives the
program's ``make_optimizer('eva', ...)``; ``fused`` chooses how the program
computes the same update, so the reference ignores it.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


class Reference:
    """Per-leaf state and update of one weight slice (a layer's 2-D W, or a
    vector); the model's reference walks the leaves and sums the partials."""

    def __init__(self, lr: float, gamma: float, kv_decay: float,
                 kl_kappa: float, momentum: float, fused: bool = False):
        del fused
        self.lr, self.kappa = lr, kl_kappa
        hi = jax.lax.Precision.HIGHEST

        @jax.jit
        def fold(state, g, stats, count):
            p = g
            if stats is not None:
                a_run = kv_decay * state['a'] + (1 - kv_decay) * stats[0]
                b_run = kv_decay * state['b'] + (1 - kv_decay) * stats[1]
                state = dict(state, a=a_run, b=b_run)
                corr = 1.0 - kv_decay ** count
                a, b = a_run / corr, b_run / corr
                dot = jnp.einsum('io,i,o->', g, a, b, precision=hi)
                coeff = dot / (gamma + jnp.sum(a * a) * jnp.sum(b * b))
                p = (g - coeff * a[:, None] * b[None, :]) / gamma
            m = momentum * state['m'] + p
            return dict(state, m=m), jnp.sum(m * g)

        @functools.partial(jax.jit, donate_argnums=(1,))
        def apply(theta, state, nu, count):
            m = nu * state['m']
            return ((theta.astype(jnp.float32) - lr * m).astype(theta.dtype),
                    dict(state, m=m))

        self.fold, self.apply = fold, apply

    @staticmethod
    def init(shape: tuple, has_stats: bool) -> dict:
        state = {'m': jnp.zeros(shape, jnp.float32)}
        if has_stats:
            state['a'] = jnp.zeros(shape[-2], jnp.float32)
            state['b'] = jnp.zeros(shape[-1], jnp.float32)
        return state

    def factor(self, partial) -> float:
        """ν, the KL clip over all leaves, from the summed <m, G>."""
        kl = max(float(partial), 0.0)
        return min(1.0, math.sqrt(self.kappa / max(self.lr ** 2 * kl, 1e-20)))

    @staticmethod
    def first_update(state: dict):
        return state['m']


def program_first_update(opt_state, params):
    """The program's momentum trace: the one subtree of its optimizer state
    shaped like the parameters, wherever the chain keeps it."""
    from bench.harness import param_shaped
    found = param_shaped(opt_state, params)
    if len(found) != 1:
        raise RuntimeError(f'{len(found)} parameter-shaped subtrees in the '
                           'optimizer state, expected the momentum trace')
    return found[0]
