"""Bring-up smoke test of the Eva trainer on TPU.

    python chip_smoke.py               # one chip: device, kernels, trainer
    python chip_smoke.py --four-chips  # four chips: data-parallel path only

One process; it starts no child that touches JAX.  Each phase prints what it
found.  The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``,
printed only when every phase passed.  Without a TPU, or outside a checkout
of this repository, the script exits non-zero and prints no result.

Phases (one chip, in this order):
  device   — JAX must report a TPU.
  trainer  — ``repro.launch.train.main`` on qwen2-0.5b (published widths,
             random weights from seed 0) with Eva, seq 2048: once on the
             default path (inline jnp), once with ``--fused``, where the
             backend rule runs compiled Pallas ``eva_fused`` inside a real
             step.  Losses finite and falling.
  kernels  — the two fused Eva kernels through ``kernels.dispatch``,
             compiled for the chip, at qwen2-0.5b's preconditioned bucket
             shapes in bf16 and f32, against ``kernels/ref.py``
             (tolerance ``TOL``).

Phases (``--four-chips``): W=4 against W=1 on the same global batch — Eva
on qwen2-0.5b through ``Trainer.fit_elastic``, and K-FAC on demo-base
through the explicit data-parallel step (``train.compression``) with the
curvature refresh sharded over the workers and int8 gradient, statistics and
refresh codecs.
"""
from __future__ import annotations

import argparse
import json
import math
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / 'runs' / 'chip_smoke'

GAMMA, MU = 0.03, 0.9
# Kernel-vs-reference tolerance.  Fused outputs carry the coefficient's
# reduction error into every element, so they are held to 1e-5 of max |out|;
# their aux sums to 1e-5 of the sum of the magnitudes of their terms.  A
# wrong tile or slice is off by O(1).
TOL = 1e-5
# x 2048 tokens: the largest batch whose step the TPU compiler fits in one
# v5e's 15.75 GiB (peak 14.37 GiB at 4; it refuses 5 with 17.29G used)
SMOKE_BATCH = 4
TRAIN_STEPS = 32
ELASTIC_STEPS = 24


def fail(msg: str) -> None:
    raise SystemExit(f'chip_smoke: FAILED: {msg}')


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


# ---------------------------------------------------------------------------
# device


def device_phase(count: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != 'tpu':
        fail(f'no TPU found: JAX reports platform {devs[0].platform!r}')
    check(len(devs) >= count, f'need {count} chips, JAX reports {len(devs)}')
    print(f'[device] {devs[0].device_kind} x{len(devs)}', flush=True)
    return devs


def peak_gb(dev) -> float:
    return dev.memory_stats()['peak_bytes_in_use'] / 1e9


# ---------------------------------------------------------------------------
# kernels


def qwen_kernel_shapes():
    """(L, d_in, d_out) of qwen2-0.5b's preconditioned buckets."""
    import jax
    from repro.configs import get_config
    from repro.core import bucketing
    from repro.core import kv as kvlib
    from repro.models import build_model
    from repro.models import module as M
    model = build_model(get_config('qwen2-0.5b'))
    flat = kvlib.flatten_params(jax.eval_shape(
        lambda: M.init_params(model.param_specs(), jax.random.PRNGKey(0))))
    plan = bucketing.build_plan({p: flat[p] for p in model.precon_paths()})
    return bucketing.kernel_shapes(plan)


def _kernel_cases(L, m, n, dt, key):
    """name -> (kernel fn, reference fn, args)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import dispatch as kd
    from repro.kernels import ref

    ks = jax.random.split(key, 4)
    g = jax.random.normal(ks[0], (L, m, n), jnp.float32).astype(dt)
    a = jax.random.normal(ks[1], (L, m), jnp.float32)
    b = jax.random.normal(ks[2], (L, n), jnp.float32)
    mom = jax.random.normal(ks[3], (L, m, n), jnp.float32)
    return {
        'eva_fused_stacked': (
            lambda g_, a_, b_, m_: kd.eva_fused(g_, a_, b_, GAMMA, m_, MU),
            lambda g_, a_, b_, m_: ref.eva_fused_ref(g_, a_, b_, GAMMA, m_, MU),
            (g, a, b, mom)),
        'eva_f_fused_stacked': (
            lambda g_, a_, m_: kd.eva_f_fused(g_, a_, GAMMA, m_, MU),
            lambda g_, a_, m_: ref.eva_f_fused_ref(g_, a_, GAMMA, m_, MU),
            (g, a, mom)),
    }


def _fused_err(g, k, r):
    """Output error relative to max |out|; aux error relative to the sum of
    the magnitudes of each partial's terms."""
    import jax.numpy as jnp
    (ko, ka), (ro, ra) = k, r
    g32 = g.astype(jnp.float32)
    terms = jnp.stack([jnp.sum(jnp.abs(ro * g32), (-2, -1)),
                       jnp.sum(ro * ro, (-2, -1)),
                       jnp.sum(g32 * g32, (-2, -1))], -1)
    e_out = jnp.max(jnp.abs(ko - ro)) / jnp.max(jnp.abs(ro))
    return jnp.maximum(e_out, jnp.max(jnp.abs(ka - ra) / terms))


def kernel_phase():
    import jax
    import jax.numpy as jnp
    from repro.kernels import dispatch as kd

    check(kd.backend() == 'tpu', f'backend {kd.backend()!r} takes the jnp '
          'twins, not the kernels')
    shapes = qwen_kernel_shapes()
    print(f'[kernels] qwen2-0.5b buckets (L, d_in, d_out): {shapes}',
          flush=True)
    key = jax.random.PRNGKey(0)
    n_ok = 0
    for L, m, n in shapes:
        for dt in (jnp.bfloat16, jnp.float32):
            key, sub = jax.random.split(key)
            dname = jnp.dtype(dt).name
            for name, (kern, reff, args) in _kernel_cases(
                    L, m, n, dt, sub).items():
                compiled = jax.jit(kern).lower(*args).compile()
                check(name in compiled.as_text(),
                      f'{name}: no such kernel in the compiled call')
                k = compiled(*args)
                with jax.default_matmul_precision('highest'):
                    r = jax.jit(reff)(*args)
                err = float(_fused_err(args[0], k, r))
                print(f'[kernels] {name:20s} {L}x{m}x{n} {dname:8s} '
                      f'err {err:.3e} (tol {TOL:.1e})', flush=True)
                check(math.isfinite(err) and err <= TOL,
                      f'{name} {L}x{m}x{n} {dname}: err {err} > tol {TOL}')
                n_ok += 1
    print(f'[kernels] {n_ok} compiled pallas calls within tolerance',
          flush=True)


# ---------------------------------------------------------------------------
# trainer


def _metrics(run_dir: Path) -> list[dict]:
    with (run_dir / 'metrics.jsonl').open() as f:
        recs = [json.loads(line) for line in f]
    return [r for r in recs if r.get('event') == 'step']


def trainer_phase():
    import jax
    from repro.kernels import dispatch as kd
    from repro.launch import train

    base = ['--arch', 'qwen2-0.5b', '--opt', 'eva', '--seq-len', '2048',
            '--batch', str(SMOKE_BATCH), '--steps', str(TRAIN_STEPS),
            '--ckpt-every', '0', '--log-every', '1']
    for tag, extra in (('default', []),
                       ('fused', ['--fused'])):
        out = OUT / tag
        shutil.rmtree(out, ignore_errors=True)
        losses = train.main(base + extra + ['--out-dir', str(out)])
        steps = _metrics(out / 'qwen2-0.5b-eva')
        times = [r['step_time_s'] for r in steps[1:]]
        check(all(math.isfinite(x) for x in losses),
              f'{tag}: non-finite loss {losses}')
        check(losses[-1] < losses[0],
              f'{tag}: loss did not fall: {losses[0]} -> {losses[-1]}')
        impl = 'inline jnp'
        if extra:
            impl = (f'backend {kd.backend()} -> '
                    + ('compiled Pallas eva_fused_stacked, '
                       f'{kd.BLOCK}-wide blocks' if kd.backend() == 'tpu'
                       else 'jnp twin eva_fused_ref'))
        print(f'[trainer] {tag}: batch {SMOKE_BATCH}x2048, losses '
              + ' '.join(f'{x:.4f}' for x in losses), flush=True)
        print(f'[trainer] {tag}: step times after the first (s) {times}',
              flush=True)
        print(f'[trainer] {tag}: kernels {impl}', flush=True)
        print(f'[trainer] {tag}: peak_bytes_in_use '
              f'{jax.devices()[0].memory_stats()["peak_bytes_in_use"]}',
              flush=True)


# ---------------------------------------------------------------------------
# four chips


def _model_and_data(arch, batch, seq_len):
    import jax
    from repro.configs import get_config
    from repro.configs.registry import demo_lm
    from repro.data import LMStream
    from repro.models import build_model
    from repro.models import module as M

    cfg = demo_lm(arch.split('-', 1)[1]) if arch.startswith('demo-') \
        else get_config(arch)
    model = build_model(cfg)
    params = M.init_params(model.param_specs(), jax.random.PRNGKey(0))
    return model, params, LMStream(vocab=cfg.vocab, seq_len=seq_len,
                                   batch=batch, seed=0)


def _elastic(arch, batch, seq_len, steps, world):
    """Eva through ``Trainer.fit_elastic`` at ``world``; (losses, params)."""
    from repro.core import make_optimizer
    from repro.train import Trainer, TrainerConfig

    model, params, data = _model_and_data(arch, batch, seq_len)
    opt, capture = make_optimizer('eva', lr=0.05)
    out = OUT / f'{arch}-eva-w{world}'
    shutil.rmtree(out, ignore_errors=True)
    tc = TrainerConfig(total_steps=steps, log_every=steps, ckpt_every=0,
                       out_dir=str(out))
    trainer = Trainer(model, opt, capture, tc)
    params, _, hist = trainer.fit_elastic(params, data, world=world)
    return [loss for _, loss in hist], params


def _kfac_int8_dp(arch, batch, seq_len, steps, world):
    """K-FAC through the explicit-DP step with int8 gradient, statistics
    and refresh codecs and the refresh sharded over ``world``;
    (losses, params, max comm saturation)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.comm.exchange import ExchangeConfig
    from repro.core import kv as kvlib
    from repro.core import make_optimizer
    from repro.launch.mesh import make_data_mesh
    from repro.schedule.runtime import RefreshRuntime
    from repro.train.compression import make_dp_train_step
    from repro.train.step import init_opt_state

    model, params, data = _model_and_data(arch, batch, seq_len)
    opt, capture = make_optimizer('kfac', lr=0.05)
    paths = set(model.precon_paths()) & set(kvlib.flatten_params(params))
    taps_fn = lambda p: kvlib.make_full_taps(p, paths,
                                             (batch // world, seq_len))
    comm = ExchangeConfig(grads='int8', stats='int8', codec='int8')
    sched = RefreshRuntime(shard_refresh=True)
    mesh = make_data_mesh(world)
    step_fn, init_err = make_dp_train_step(model, opt, capture, mesh,
                                           taps_fn=taps_fn, comm=comm,
                                           sched=sched)
    full_taps = lambda p, b: kvlib.make_full_taps(p, paths,
                                                  b['tokens'].shape)
    state = init_opt_state(model, opt, capture, params, data.batch_at(0),
                           taps_fn=full_taps, sched=sched, comm=comm)
    rep = NamedSharding(mesh, P())
    params, state = jax.device_put((params, state), rep)
    err = jax.device_put(init_err(params), rep)
    put_batch = lambda t: jax.device_put(data.batch_at(t),
                                         NamedSharding(mesh, P('data')))
    step_fn = jax.jit(step_fn).lower(params, state, err,
                                     put_batch(0)).compile()
    if world > 1:
        hlo = step_fn.as_text()
        check('all-gather' in hlo and 'all-reduce' in hlo,
              f'kfac W={world}: no all-gather/all-reduce in the compiled step')
    losses, sat = [], 0.0
    for t in range(steps):
        params, state, err, m = step_fn(params, state, err, put_batch(t))
        losses.append(float(m['loss']))
        sat = max(sat, float(m['comm_saturation']))
    return losses, params, sat


def _compare(name, l1, l4, rtol0, rtol):
    rel = [abs(a - b) / abs(a) for a, b in zip(l1, l4)]
    print(f'[four-chips] {name}: W=1 ' + ' '.join(f'{x:.5f}' for x in l1),
          flush=True)
    print(f'[four-chips] {name}: W=4 ' + ' '.join(f'{x:.5f}' for x in l4),
          flush=True)
    print(f'[four-chips] {name}: rel diff step 0 {rel[0]:.3e} (tol '
          f'{rtol0:.0e}), max {max(rel):.3e} (tol {rtol:.0e})', flush=True)
    check(all(math.isfinite(x) for x in l1 + l4), f'{name}: non-finite loss')
    check(rel[0] <= rtol0, f'{name}: step-0 rel diff {rel[0]} > {rtol0}')
    check(max(rel) <= rtol, f'{name}: W=4 vs W=1 rel diff {max(rel)} > {rtol}')
    check(l4[-1] < l4[0], f'{name}: W=4 loss did not fall')


def _check_spans_all(params, devs):
    import jax
    want = set(devs[:4])
    for leaf in jax.tree_util.tree_leaves(params):
        check(set(leaf.sharding.device_set) == want,
              f'a W=4 parameter lives on {leaf.sharding.device_set}, not the '
              f'four chips')


def four_chip_phase(devs):
    # Same global batch, same seed, same initial params: at step 0 W=4 and
    # W=1 differ only in how the loss mean is grouped (four per-worker
    # means, then their mean), so they agree to well within 1e-3 relative.
    # From step 1 on the updates differ by rounding: for Eva
    # (fit_elastic) W=4 averages four per-worker bf16 gradients and
    # statistics in f32 and rounds the mean back to bf16 (2^-9 relative
    # per element) where W=1 rounds the whole-batch values once; for K-FAC
    # each side quantizes what it exchanges to int8 (a step of 1/127 of
    # the tensor's max, with error feedback), at W=4 per worker, at W=1
    # once.  Each step is bounded (Eva's trust region, K-FAC's damping), so
    # over these steps such rounding parts the losses by far less than 1e-2
    # relative (2.5e-4 for this K-FAC run on four CPU devices), while a
    # lost or doubled worker shard moves the step-0 loss by the
    # batch-to-batch spread and later steps by the size of an update.
    l1, _ = _elastic('qwen2-0.5b', 4, 1024, ELASTIC_STEPS, world=1)
    l4, p4 = _elastic('qwen2-0.5b', 4, 1024, ELASTIC_STEPS, world=4)
    _check_spans_all(p4, devs)
    _compare('eva qwen2-0.5b fit_elastic (batch 4x1024)', l1, l4, 1e-3,
             1e-2)
    del p4
    l1, _, s1 = _kfac_int8_dp('demo-base', 16, 128, ELASTIC_STEPS, world=1)
    l4, p4, s4 = _kfac_int8_dp('demo-base', 16, 128, ELASTIC_STEPS, world=4)
    _check_spans_all(p4, devs)
    _compare('kfac demo-base int8 grads/stats/refresh, sharded refresh '
             '(batch 16x128)', l1, l4, 1e-3, 1e-2)
    print(f'[four-chips] int8 gradient codec saturation W=1 {s1} W=4 {s4}',
          flush=True)
    check(s4 == 0.0, f'int8 gradient codec saturated: {s4}')
    peaks = [f'{peak_gb(d):.3f}' for d in devs[:4]]
    print(f'[four-chips] peak GB per chip {peaks}', flush=True)
    check(all(peak_gb(d) > 0.5 for d in devs[:4]),
          f'a chip held almost nothing: peaks {peaks} GB')


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--four-chips', action='store_true',
                    help='run only the data-parallel path on four chips '
                         'against its one-chip reference')
    args = ap.parse_args(argv)

    src = ROOT / 'src'
    if not (src / 'repro').is_dir():
        fail(f'no repository sources at {src}: run from a checkout')
    sys.path.insert(0, str(src))
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    devs = device_phase(4 if args.four_chips else 1)
    print(f'[device] compile cache {cache}', flush=True)
    if args.four_chips:
        four_chip_phase(devs)
    else:
        # the trainer first: its peak bytes and its kernel choices are then
        # its own, not left over from the kernel phase
        trainer_phase()
        kernel_phase()
    print(json.dumps({'ok': True, 'device': {
        'platform': devs[0].platform, 'kind': devs[0].device_kind,
        'count': len(devs)}}))
    return 0


if __name__ == '__main__':
    sys.exit(main())
