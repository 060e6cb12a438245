"""Paper Table 5: per-iteration time and memory relative to SGD.

Two sections:
  * transformer LM (demo config) — SGD / Eva / Eva-f / Eva-s / Shampoo@1 /
    Shampoo@10 / AdamW (K-FAC's full-tap capture targets the MLP section;
    see DESIGN.md §4.1),
  * MLP — adds K-FAC@1 / K-FAC@10 / FOOF (explicit inverses).
Derived: time and optimizer-state memory relative to SGD — the paper's
headline "Eva ≈ 1.14× SGD time, ~1.0× memory; K-FAC/Shampoo ≫".

``--bucketed`` adds a third section isolating the preconditioning stage on
a 24-layer qwen2-0.5b-proportioned transformer: per-LAYER loop (one call
per layer per projection — what a hook-based implementation pays) vs
per-PATH loop (broadcast over the scan stack, the pre-bucketing repo
state) vs the bucketed ``precondition_tree`` (one call per (shape, dtype)
bucket), with the launch counts that explain the gap.

``--refresh-sharding`` isolates the curvature *refresh* stage (K-FAC damped
inverses for the same 24-layer config) under a W=4 host-device data mesh:
every-worker-redundant recomputation (the pre-runtime behavior) vs
worker-sharded ownership with the owned-slice gather exchange (default)
and the legacy full-stack psum — plus the exchanged-bytes-per-refresh
table for psum vs gather × codec (identity/bf16/int8), the ROADMAP
"Refresh-exchange volume" numbers.

``--factor-sharding`` isolates the oversized-factor *apply* stage under the
same W=4 mesh: the legacy cached two-sided contraction vs the
``head_policy`` ladder from ``repro.core.factor_sharded`` — 'exclude'
(identity guard) and 'shard' (matrix-free distributed solve; CG at K-FAC's
power −1, binomial series at Shampoo's −1/4) — with the shard rows'
deviation from the dense reference asserted as a CI bound.
"""
from __future__ import annotations

import os
import sys

if ('--refresh-sharding' in sys.argv     # must precede the first jax import
        or '--factor-sharding' in sys.argv
        or '--pipeline' in sys.argv):
    _flags = os.environ.get('XLA_FLAGS', '')
    if '--xla_force_host_platform_device_count' not in _flags:
        os.environ['XLA_FLAGS'] = (
            _flags + ' --xla_force_host_platform_device_count=4').strip()

import argparse

import jax
import jax.numpy as jnp

from benchmarks.common import emit, time_fn, tree_bytes, write_json
from repro.configs.base import ArchConfig
from repro.configs.registry import demo_lm
from repro.core import bucketing
from repro.core import kv as kvlib
from repro.core import precondition as pre
from repro.core.registry import make_optimizer
from repro.data.synthetic import ClassStream, LMStream
from repro.models import build_model
from repro.models import module as M
from repro.models.simple import MLP, classifier_loss_fn
from repro.train.step import init_opt_state, make_train_step


def _bench(model, params, batch, name, taps_batch=None, **opt_kw):
    opt, capture = make_optimizer(name.split('@')[0], lr=0.01, **opt_kw)
    taps_fn = None
    if capture.needs_taps and hasattr(model, 'make_taps'):
        taps_fn = lambda p: model.make_taps(taps_batch, capture)  # noqa: E731
    state = init_opt_state(model, opt, capture, params, batch, taps_fn=taps_fn)
    step = jax.jit(make_train_step(model, opt, capture, taps_fn=taps_fn))
    t = time_fn(step, params, state, batch)
    return t, tree_bytes(state)


def _bench_config() -> ArchConfig:
    """qwen2-0.5b layer structure (24L, GQA, SwiGLU) at 1/4 width so the
    CPU interpret path finishes in benchmark time; the bucket structure —
    what the comparison measures — is identical to the full model's."""
    return ArchConfig(name='qwen2-0.5b-bench', family='dense', n_layers=24,
                      d_model=224, n_heads=7, n_kv_heads=1, d_ff=1216,
                      vocab=2048)


def run_bucketed(method: str = 'eva') -> None:
    cfg = _bench_config()
    model = build_model(cfg)
    flat_specs = M.flatten_specs(model.param_specs())
    paths = sorted(set(model.precon_paths()) & set(flat_specs))
    key = jax.random.PRNGKey(0)
    grads, aux = {}, {}
    for i, p in enumerate(paths):
        shape = flat_specs[p].shape
        ks = jax.random.split(jax.random.fold_in(key, i), 3)
        grads[p] = jax.random.normal(ks[0], shape, jnp.float32)
        aux[p] = kvlib.LayerStats(
            a_mean=jax.random.normal(ks[1], shape[:-1], jnp.float32),
            b_mean=jax.random.normal(ks[2], shape[:-2] + shape[-1:],
                                     jnp.float32))
    plan = bucketing.build_plan(grads)
    n_layers = sum(
        (flat_specs[p].shape[0] if len(flat_specs[p].shape) == 3 else 1)
        for p in paths)

    def per_layer(g, a):
        out = {}
        for p in paths:
            if g[p].ndim == 3:   # unstack the scan dim: one call per layer
                out[p] = jnp.stack([
                    pre.eva_precondition(g[p][l], a[p].a_mean[l],
                                         a[p].b_mean[l], 0.03)
                    for l in range(g[p].shape[0])])
            else:
                out[p] = pre.eva_precondition(g[p], a[p].a_mean,
                                              a[p].b_mean, 0.03)
        return out

    def per_path(g, a):
        return {p: pre.eva_precondition(g[p], a[p].a_mean, a[p].b_mean, 0.03)
                for p in paths}

    def bucketed(p):
        return lambda g, a: pre.precondition_tree(g, a, method, 0.03, plan=p)

    def launches(p):
        return sum(1 if b.stacked else len(b.paths) for b in p.buckets)

    # pure bucketing (every bucket stacked) vs the tuned plan (default
    # min_bucket_size: N<=2 buckets skip gather/scatter — the ROADMAP
    # "bucket gather cost" item; at this config every bucket is small, so
    # the tuned plan degenerates to per-path, which is the point on CPU)
    plan_pure = bucketing.build_plan(grads, min_bucket_size=1)
    t_layer = time_fn(jax.jit(per_layer), grads, aux)
    t_path = time_fn(jax.jit(per_path), grads, aux)
    t_pure = time_fn(jax.jit(bucketed(plan_pure)), grads, aux)
    t_tuned = time_fn(jax.jit(bucketed(plan)), grads, aux)
    emit(f'table5/precon/{cfg.name}/per_layer', t_layer,
         f'launches={n_layers}')
    emit(f'table5/precon/{cfg.name}/per_path', t_path,
         f'launches={len(paths)}')
    emit(f'table5/precon/{cfg.name}/bucketed', t_pure,
         f'launches={launches(plan_pure)};speedup_vs_per_layer='
         f'{t_layer / max(t_pure, 1e-9):.2f}x;'
         f'speedup_vs_per_path={t_path / max(t_pure, 1e-9):.2f}x')
    emit(f'table5/precon/{cfg.name}/bucketed_tuned', t_tuned,
         f'launches={launches(plan)};min_bucket_size=default;'
         f'speedup_vs_per_layer={t_layer / max(t_tuned, 1e-9):.2f}x;'
         f'speedup_vs_bucketed={t_pure / max(t_tuned, 1e-9):.2f}x')


def run_refresh_sharding() -> None:
    """K-FAC inverse refresh for the 24-layer bench config on a (4,)-'data'
    host mesh: redundant (every worker inverts every bucket item) vs
    worker-sharded (each worker inverts only its owned slices) under both
    exchange modes (owned-slice gather / full-stack psum).  Wall time
    includes the exchange, so the printed speedup is the end-to-end
    refresh win, not just the FLOP ratio; the bytes table quantifies the
    wire volume each mode × codec moves."""
    from jax.sharding import PartitionSpec as P

    from repro.comm import exchange
    from repro.comm.exchange import ExchangeConfig
    from repro.core.precondition import kfac_pi_damping
    from repro.schedule import ownership
    from repro.schedule import runtime as schedrt
    from repro.launch.mesh import make_mesh

    cfg = _bench_config()
    model = build_model(cfg)
    flat_specs = M.flatten_specs(model.param_specs())
    paths = sorted(set(model.precon_paths()) & set(flat_specs))
    key = jax.random.PRNGKey(0)
    grads = {p: jax.random.normal(jax.random.fold_in(key, i),
                                  flat_specs[p].shape, jnp.float32)
             for i, p in enumerate(paths)}
    plan = bucketing.build_plan(grads)

    def psd(k, *shape):
        m = jax.random.normal(k, shape)
        return m @ jnp.swapaxes(m, -1, -2) + 0.1 * jnp.eye(shape[-1])

    stats, old = {}, {}
    for i, b in enumerate(plan.buckets):
        k1, k2 = jax.random.split(jax.random.fold_in(key, 1000 + i))
        lead = (len(b.paths),) + b.shape[:-2]
        d_in, d_out = b.shape[-2], b.shape[-1]
        ao = psd(k1, *lead, d_in, d_in)
        bo = psd(k2, *lead, d_out, d_out)
        stats[b.key] = (ao, bo)
        old[b.key] = (jnp.zeros_like(ao), jnp.zeros_like(bo))

    def one(b, args):
        ao, bo = args
        gamma_r, gamma_q = kfac_pi_damping(ao, bo, 0.03)
        eye_a = jnp.eye(ao.shape[-1], dtype=jnp.float32)
        eye_b = jnp.eye(bo.shape[-1], dtype=jnp.float32)
        return (jnp.linalg.inv(ao + gamma_r[..., None, None] * eye_a),
                jnp.linalg.inv(bo + gamma_q[..., None, None] * eye_b))

    if jax.device_count() < 2:
        raise SystemExit('refresh-sharding cell needs multiple host devices '
                         f'(got {jax.device_count()}; check XLA_FLAGS)')
    mesh = make_mesh((jax.device_count(),), ('data',))

    def refresh(shard, comm=None):
        def body(s, o):
            return schedrt.sharded_refresh(
                plan, jnp.asarray(True), one, s, o,
                cost=ownership.inverse_cost('both'), shard=shard, comm=comm)
        return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(), P()),
                                     out_specs=P(), check_vma=False))

    t_red = time_fn(refresh(False), stats, old)
    t_shard = time_fn(refresh(True), stats, old)           # default: gather
    t_psum = time_fn(refresh(True, comm=ExchangeConfig(exchange='psum')),
                     stats, old)
    world = jax.device_count()
    n_slices = sum(len(b.paths) * ownership.lead_size(b)
                   for b in plan.buckets)
    emit(f'table5/refresh/{cfg.name}/redundant_w{world}', t_red,
         f'slices_per_worker={n_slices}')
    per_worker = {w: 0 for w in range(world)}
    for counts in ownership.describe_ownership(plan, world).values():
        for w, c in enumerate(counts):
            per_worker[w] += c
    emit(f'table5/refresh/{cfg.name}/sharded_w{world}', t_shard,
         f'slices_per_worker={max(per_worker.values())};'
         f'speedup={t_red / max(t_shard, 1e-9):.2f}x')
    emit(f'table5/refresh/{cfg.name}/sharded_psum_w{world}', t_psum,
         f'slices_per_worker={max(per_worker.values())};'
         f'speedup={t_red / max(t_psum, 1e-9):.2f}x')

    # --- exchange bytes per refresh: psum vs gather × codec (the ROADMAP
    # "Refresh-exchange volume" numbers; logical per-worker bytes from the
    # same repro.comm accounting the runtime records at trace time) ---
    owners = ownership.assign_slice_owners(plan,
                                           ownership.inverse_cost('both'),
                                           world)
    inv_stacks = exchange.slice_stack_specs(plan, 'both')
    psum_b = exchange.refresh_exchange_bytes(plan, owners, inv_stacks, world,
                                             mode='psum')
    emit(f'table5/refresh_bytes/{cfg.name}/psum_w{world}', 0.0,
         f'bytes_per_refresh={psum_b}')
    for codec in ('identity', 'bf16', 'int8'):
        g_b = exchange.refresh_exchange_bytes(plan, owners, inv_stacks,
                                              world, codec=codec,
                                              mode='gather')
        emit(f'table5/refresh_bytes/{cfg.name}/gather_{codec}_w{world}', 0.0,
             f'bytes_per_refresh={g_b};'
             f'reduction_vs_psum={psum_b / g_b:.2f}x')


def run_factor_sharding() -> None:
    """Per-step apply of one head-proportioned bucket (in-dim dense, out-dim
    tripping the sub-slice threshold) on a W=4 host-device data mesh: the
    legacy cached two-sided einsum vs ``head_policy='exclude'`` (identity
    guard) vs ``'shard'`` (matrix-free distributed solve — CG at K-FAC's
    power −1, binomial series at Shampoo's −1/4).  Each shard row reports
    its max deviation from the dense reference (the iterative-tolerance
    bound the tests pin) and the static partial-psum bytes the solve pays."""
    from jax.sharding import PartitionSpec as P

    from repro.core import factor_sharded as fsh
    from repro.core.precondition import kfac_pi_damping
    from repro.launch.mesh import make_mesh

    if jax.device_count() < 2:
        raise SystemExit('factor-sharding cell needs multiple host devices '
                         f'(got {jax.device_count()}; check XLA_FLAGS)')
    mesh = make_mesh((jax.device_count(),), ('data',))
    world = jax.device_count()

    key = jax.random.PRNGKey(0)
    d_in, d_out = 48, 384
    flat = {'head/w': jax.random.normal(key, (d_in, d_out), jnp.float32)}
    plan = bucketing.build_plan(flat)
    (bucket,) = plan.buckets

    def psd(k, d):
        m = jax.random.normal(k, (d, d))
        return m @ m.T / d + 0.5 * jnp.eye(d)

    k1, k2 = jax.random.split(jax.random.fold_in(key, 1))
    m_in = psd(k1, d_in)[None]     # bucket batch dim (N=1 path)
    m_out = psd(k2, d_out)[None]
    factors = {bucket.key: (m_in, m_out)}
    gamma = 0.03

    def smap(body):
        return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(),),
                                     out_specs=P(), check_vma=False))

    def sharded(method, power, solver, iters):
        cfg = fsh.FactorShardConfig(head_policy='shard', shard_threshold=256,
                                    solver=solver, solve_iters=iters)
        _, pol = fsh.split_plan(plan, cfg)
        head = fsh.init_head(factors, pol, cfg, plan, method)
        head = fsh.refresh_head(jnp.asarray(True), factors, head, pol, gamma,
                                cfg=cfg, plan=plan, method=method)
        fn = smap(lambda g: fsh.apply_tree(g, plan, pol, head, factors,
                                           power=power, cfg=cfg,
                                           site='factor/bench')['head/w'])
        return fn, fsh.shard_psum_bytes(plan, pol, cfg)

    # --- K-FAC (power −1): cached dense inverses vs exclude vs CG solve ---
    gamma_r, gamma_q = kfac_pi_damping(m_in, m_out, gamma)
    a_inv = jnp.linalg.inv(m_in + gamma_r[..., None, None] * jnp.eye(d_in))
    b_inv = jnp.linalg.inv(m_out + gamma_q[..., None, None] * jnp.eye(d_out))
    ops = {bucket.key: kvlib.LayerStats(a_outer=a_inv, b_outer=b_inv)}
    dense_fn = smap(lambda g: pre.precondition_tree(
        g, ops, 'kfac_cached', gamma, plan=plan)['head/w'])

    ecfg = fsh.FactorShardConfig(head_policy='exclude', shard_threshold=256)
    _, epol = fsh.split_plan(plan, ecfg)
    ehead = fsh.refresh_head(jnp.asarray(True), factors,
                             fsh.init_head(factors, epol, ecfg, plan, 'kfac'),
                             epol, gamma, cfg=ecfg, plan=plan, method='kfac')
    excl_fn = smap(lambda g: fsh.apply_tree(g, plan, epol, ehead, factors,
                                            power=1.0, cfg=ecfg,
                                            site='factor/bench')['head/w'])
    cg_fn, cg_bytes = sharded('kfac', 1.0, 'cg', 32)

    ref = dense_fn(flat)
    t_dense = time_fn(dense_fn, flat)
    t_excl = time_fn(excl_fn, flat)
    t_cg = time_fn(cg_fn, flat)
    cg_dev = float(jnp.max(jnp.abs(cg_fn(flat) - ref)))
    emit(f'table5/factor/kfac/dense_w{world}', t_dense,
         f'd_out={d_out};cached_two_sided=1')
    emit(f'table5/factor/kfac/exclude_w{world}', t_excl,
         f'd_out={d_out};speedup_vs_dense={t_dense / max(t_excl, 1e-9):.2f}x')
    emit(f'table5/factor/kfac/shard_cg_w{world}', t_cg,
         f'd_out={d_out};iters=32;maxdiff_vs_dense={cg_dev:.2e};'
         f'solve_psum_bytes={cg_bytes:.0f}')
    if cg_dev > 1e-4:
        raise SystemExit(f'factor-sharding cell: CG solve deviates '
                         f'{cg_dev:.2e} from the dense inverse (>1e-4)')

    # --- Shampoo (power −1/4): cached eigh roots vs binomial series ---
    p_in = pre._inv_proot_psd(m_in, gamma, 0.25)
    p_out = pre._inv_proot_psd(m_out, gamma, 0.25)
    sops = {bucket.key: kvlib.LayerStats(a_outer=p_in, b_outer=p_out)}
    sdense_fn = smap(lambda g: pre.precondition_tree(
        g, sops, 'shampoo_cached', gamma, plan=plan)['head/w'])
    bin_fn, bin_bytes = sharded('shampoo', 0.25, 'binomial', 200)

    sref = sdense_fn(flat)
    t_sdense = time_fn(sdense_fn, flat)
    t_bin = time_fn(bin_fn, flat)
    bin_dev = float(jnp.max(jnp.abs(bin_fn(flat) - sref)))
    emit(f'table5/factor/shampoo/dense_w{world}', t_sdense,
         f'd_out={d_out};cached_eigh_roots=1')
    emit(f'table5/factor/shampoo/shard_binomial_w{world}', t_bin,
         f'd_out={d_out};iters=200;maxdiff_vs_dense={bin_dev:.2e};'
         f'solve_psum_bytes={bin_bytes:.0f}')
    if bin_dev > 1e-3:
        raise SystemExit(f'factor-sharding cell: binomial −1/4 solve '
                         f'deviates {bin_dev:.2e} from the eigh root (>1e-3)')


def run_pipeline(check_overlap: bool = False) -> None:
    """Sync vs onestep curvature pipeline on a W=4 host-device data mesh.

    Times the full explicit-DP train step (``make_dp_train_step``) for eva
    (stats pmean site) on the demo LM and for K-FAC (codec'd stats reduce +
    owned-slice refresh gather) on the MLP, in both pipeline modes, and
    reports the HLO dependence structure: the fraction of dot FLOPs inside
    the collectives' forward cone.  On CPU the thunk runtime executes
    serially, so wall-clock gains are muted — the dependence collapse
    (sync ≈ 1.0 → onestep ≈ 0.0) is the backend-independent evidence that
    an async-collective backend (TPU/GPU) can overlap the exchange, and is
    what ``--check-overlap`` asserts for CI."""
    from jax.sharding import PartitionSpec as P  # noqa: F401 (mesh check)

    from repro.launch import hlo_analysis
    from repro.schedule.runtime import RefreshRuntime
    from repro.launch.mesh import make_mesh
    from repro.train.compression import make_dp_train_step

    if jax.device_count() < 2:
        raise SystemExit('pipeline cell needs multiple host devices '
                         f'(got {jax.device_count()}; check XLA_FLAGS)')
    mesh = make_mesh((jax.device_count(),), ('data',))
    world = jax.device_count()

    cases = []
    cfg = demo_lm('small')
    lm = build_model(cfg)
    lm_params = M.init_params(lm.param_specs(), jax.random.PRNGKey(0))
    lm_batch = LMStream(vocab=cfg.vocab, seq_len=64, batch=16, seed=0).batch_at(0)
    cases.append(('lm/eva', lm, lm_params, lm_batch, 'eva', {}, None))

    mlp = MLP([64, 256, 256, 256, 10])
    mlp.loss_fn = classifier_loss_fn(mlp)
    mparams = M.init_params(mlp.param_specs(), jax.random.PRNGKey(1))
    mbatch = ClassStream(batch=128, dim=64, classes=10).batch_at(0)
    cases.append(('mlp/kfac', mlp, mparams, mbatch, 'kfac',
                  {'interval': 1}, 128 // world))

    failures = []
    for label, model, params, batch, name, kw, taps_batch in cases:
        opt, capture = make_optimizer(name, lr=0.01, **kw)
        taps_init = taps_step = None
        if capture.needs_taps and hasattr(model, 'make_taps'):
            # init sees the full batch; the step's taps see the per-worker
            # shard inside shard_map (batch split over 'data')
            taps_init = lambda p: model.make_taps(taps_batch * world, capture)  # noqa: B023,E731
            taps_step = lambda p: model.make_taps(taps_batch, capture)  # noqa: B023,E731
        rows = {}
        for mode in ('sync', 'onestep'):
            rt = RefreshRuntime(pipeline=mode)
            state = init_opt_state(model, opt, capture, params, batch,
                                   taps_fn=taps_init, sched=rt)
            step, init_err = make_dp_train_step(model, opt, capture, mesh,
                                                compress=False,
                                                taps_fn=taps_step, sched=rt)
            err = init_err(params)
            t = time_fn(step, params, state, err, batch)
            txt = step.lower(params, state, err, batch).compile().as_text()
            rep = hlo_analysis.collective_overlap(txt)
            rows[mode] = (t, rep)
        t_sync, rep_sync = rows['sync']
        t_one, rep_one = rows['onestep']
        emit(f'table5/pipeline/{label}/sync_w{world}', t_sync,
             f'blocking_collectives={rep_sync.blocking_collectives}'
             f'/{rep_sync.collective_count};'
             f'dep_dot_frac={rep_sync.dependent_fraction:.3f}')
        emit(f'table5/pipeline/{label}/onestep_w{world}', t_one,
             f'blocking_collectives={rep_one.blocking_collectives}'
             f'/{rep_one.collective_count};'
             f'dep_dot_frac={rep_one.dependent_fraction:.3f};'
             f'speedup_vs_sync={t_sync / max(t_one, 1e-9):.2f}x')
        # the gradient all-reduce must stay blocking (it feeds the whole
        # update — that's data parallelism, not this pipeline's concern);
        # the curvature exchanges must LEAVE the blocking set
        if rep_one.blocking_collectives >= rep_sync.blocking_collectives:
            failures.append(
                f'{label}: onestep leaves {rep_one.blocking_collectives} '
                f'collectives blocking dots (sync: '
                f'{rep_sync.blocking_collectives}) — the curvature '
                'exchanges did not leave the compute dependence cone')
    if check_overlap and failures:
        raise SystemExit('overlap check FAILED:\n  ' + '\n  '.join(failures))
    if check_overlap:
        print('# overlap check passed: onestep collectives are outside the '
              'dot dependence cone')


def run() -> None:
    # --- transformer section ---
    cfg = demo_lm('small')
    model = build_model(cfg)
    params = M.init_params(model.param_specs(), jax.random.PRNGKey(0))
    data = LMStream(vocab=cfg.vocab, seq_len=64, batch=16, seed=0)
    batch = data.batch_at(0)
    results = {}
    for name, kw in [('sgd', {}), ('eva', {}), ('eva_f', {}), ('eva_s', {}),
                     ('adamw', {}), ('shampoo@1', {'interval': 1}),
                     ('shampoo@10', {'interval': 10}), ('mfac', {'m': 8})]:
        t, mem = _bench(model, params, batch, name, **kw)
        results[name] = (t, mem)
    t_sgd, m_sgd = results['sgd']
    for name, (t, mem) in results.items():
        emit(f'table5/lm/{name}', t,
             f'rel_time={t / t_sgd:.2f};rel_state_mem={mem / max(m_sgd, 1):.2f}')

    # --- MLP section (K-FAC / FOOF need full taps) ---
    mlp = MLP([64, 256, 256, 256, 10])
    mlp.loss_fn = classifier_loss_fn(mlp)
    mparams = M.init_params(mlp.param_specs(), jax.random.PRNGKey(1))
    mbatch = ClassStream(batch=128, dim=64, classes=10).batch_at(0)
    mres = {}
    for name, kw in [('sgd', {}), ('eva', {}), ('kfac@1', {'interval': 1}),
                     ('kfac@10', {'interval': 10}), ('foof', {}),
                     ('shampoo@1', {'interval': 1})]:
        t, mem = _bench(mlp, mparams, mbatch, name, taps_batch=128, **kw)
        mres[name] = (t, mem)
    t_sgd, m_sgd = mres['sgd']
    for name, (t, mem) in mres.items():
        emit(f'table5/mlp/{name}', t,
             f'rel_time={t / t_sgd:.2f};rel_state_mem={mem / max(m_sgd, 1):.2f}')


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument('--bucketed', action='store_true',
                    help='only the bucketed-vs-per-layer preconditioning '
                         'comparison (24-layer qwen2-0.5b-proportioned)')
    ap.add_argument('--refresh-sharding', action='store_true',
                    help='only the worker-sharded curvature-refresh cell '
                         '(4 host devices, K-FAC inverses)')
    ap.add_argument('--factor-sharding', action='store_true',
                    help='only the matrix-free sharded-factor apply cell '
                         '(4 host devices, dense vs exclude vs shard)')
    ap.add_argument('--pipeline', action='store_true',
                    help='only the sync-vs-onestep curvature pipeline cell '
                         '(4 host devices, eva LM + K-FAC MLP)')
    ap.add_argument('--check-overlap', action='store_true',
                    help='with --pipeline: fail (exit 1) unless the onestep '
                         'collectives are outside the dot dependence cone')
    ap.add_argument('--json', default=None, metavar='PATH',
                    help='also write the emitted rows to PATH as JSON '
                         '(CI benchmark artifacts)')
    args = ap.parse_args()
    print('name,us_per_call,derived')
    if args.bucketed:
        run_bucketed()
    elif args.refresh_sharding:
        run_refresh_sharding()
    elif args.factor_sharding:
        run_factor_sharding()
    elif args.pipeline:
        run_pipeline(check_overlap=args.check_overlap)
    else:
        run()
    if args.json:
        write_json(args.json)


if __name__ == '__main__':
    main()
