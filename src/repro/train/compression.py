"""Error-feedback compressed gradient exchange for the explicit-DP engine.

The pjit path leaves gradient reduction to XLA (recorded in the roofline).
This engine makes the data-parallel collective explicit via ``shard_map``
over the 'data' axis so it can be codec'd: since the unified communication
layer landed, both the gradient all-reduce and the KV/KF statistics
reduction route through ``repro.comm`` — this module is the thin
train-level wrapper that picks codecs and threads the error-feedback
residual state.

Default is the int8 symmetric max-scale codec with carried error feedback
(Karimireddy et al.-style EF-SGD, which keeps convergence intact): 8× less
gradient traffic than f32 / 2× less than bf16.  Combined with Eva's
sublinear KV all-reduce this is the paper's distributed story (§3.3) plus
a beyond-paper compression layer.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.comm import exchange
from repro.core import kv as kvlib
from repro.core.transform import Extras, apply_updates
from repro.schedule import pipeline as pipemod
from repro.train.step import _plan_for_stats, compute_grads_and_stats


def quantize_allreduce(g: jnp.ndarray, err: jnp.ndarray,
                       axis: str) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Mean-all-reduce of ``g`` over ``axis`` with int8 error feedback.

    Thin wrapper over the int8+EF codec's all-reduce
    (``repro.comm.exchange.allreduce_mean_leaf``) — same op sequence as the
    historical inline implementation: global pmax scale, int8 quantize,
    exact int32-accumulate psum, shared-scale dequantize.

    Returns (averaged dequantized gradient, new local error)."""
    mean, new_err, _ = exchange.allreduce_mean_leaf(
        g, err, codec='int8', axes=(axis,))
    return mean, new_err


def make_dp_train_step(model, opt, capture: kvlib.CaptureConfig, mesh,
                       compress: bool = True, taps_fn=None,
                       comm: Optional[exchange.ExchangeConfig] = None,
                       sched=None):
    """Explicit data-parallel train step via shard_map over 'data'.

    Params/opt-state replicated; the batch is split over 'data'; gradients
    are explicitly all-reduced through ``comm.grads`` (int8+EF by default —
    the legacy ``compress`` flag maps onto the f32/int8 codecs) and the KV
    statistics through ``comm.stats`` (f32 by default — they are sublinear,
    the paper's point).  The same config threads to the optimizer through
    ``Extras.comm`` so the refresh exchange uses it too.  The step's
    metrics include ``comm_saturation`` — the int8 codec's overflow
    fraction, 0.0 by construction under the global max scale.

    ``sched`` (a ``RefreshRuntime``) threads through ``Extras.sched`` —
    pass the same one given to ``init_opt_state``; with
    ``pipeline='onestep'`` the optimizer's curvature exchanges double-buffer
    and the metrics gain the realized ``pipeline_lag`` per site.

    Returns (step_fn, init_error_fn)."""
    if comm is not None:
        from repro.comm import get_codec
        if not compress and get_codec(comm.grads).name != 'f32':
            raise ValueError(
                "conflicting arguments: compress=False but comm.grads="
                f"{comm.grads!r}; pass ExchangeConfig(grads='f32') (or drop "
                "compress=False) to say which you mean")
        cfg = comm
    else:
        cfg = exchange.ExchangeConfig(grads='int8' if compress else 'f32')

    def local_step(params, opt_state, err, batch):
        loss, grads, stats = compute_grads_and_stats(
            model, params, batch, capture,
            taps_fn(params) if taps_fn else None)
        loss = jax.lax.pmean(loss, 'data')
        grads, new_err, info = exchange.allreduce_mean_tree(
            grads, err, codec=cfg.grads, axes=('data',), site='grads/dp')
        new_err = new_err if new_err is not None else err
        # axes passed explicitly — the 'data' axis is statically known here,
        # so the reduction must not depend on the best-effort axis-env probe
        # behind pmean_stats (a false-negative there would silently leave
        # per-worker stats unreduced and desync the replicated opt state)
        stats, _, _ = exchange.allreduce_mean_tree(
            stats, codec=cfg.stats, axes=('data',), site='stats/dp')
        # stats were just reduced; lossy codecs must quantize exactly once,
        # so the optimizer's own pmean_stats call (same shard_map scope)
        # gets the idempotent f32 path
        inner = dataclasses.replace(cfg, stats='f32')
        updates, new_opt = opt.update(
            grads, opt_state, params=params,
            extras=Extras(stats=stats, loss=loss,
                          plan=_plan_for_stats(grads, stats), comm=inner,
                          sched=sched))
        new_params = apply_updates(params, updates)
        metrics = {'loss': loss, 'comm_saturation': info['saturation']}
        metrics.update(pipemod.pipeline_metrics(new_opt))
        return new_params, new_opt, new_err, metrics

    in_specs = (P(), P(), P(), P('data'))
    out_specs = (P(), P(), P(), P())
    smapped = jax.shard_map(local_step, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)

    def init_error(params):
        return jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)

    return jax.jit(smapped), init_error
