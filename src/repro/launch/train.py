"""Production training launcher.

Single-host execution of the full stack (config → model → Eva → trainer with
checkpointing/preemption).  On a multi-host deployment the same entry point
runs under ``jax.distributed.initialize()`` (``--distributed``, one process
per host); the step function, shardings and checkpoint protocol are
host-count-agnostic.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --reduced \\
        --steps 50 --opt eva
"""
from __future__ import annotations

import argparse
from typing import Optional, Sequence

import jax

from repro.configs import get_config, get_reduced
from repro.configs.registry import ARCH_IDS, demo_lm
from repro.core import kv as kvlib
from repro.core import make_optimizer
from repro.data import LMStream, Prefetcher
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.models import module as M
from repro.train import Trainer, TrainerConfig


def main(argv: Optional[Sequence[str]] = None) -> list:
    """Parse ``argv`` (default: the command line), train, and return the
    trainer's loss history."""
    ap = argparse.ArgumentParser()
    ap.add_argument('--arch', default='demo',
                    help=f'demo|demo-base|demo-100m|{"|".join(ARCH_IDS)}')
    ap.add_argument('--reduced', action='store_true',
                    help='use the reduced config (CPU-runnable)')
    ap.add_argument('--opt', default='eva')
    ap.add_argument('--lr', type=float, default=0.05)
    ap.add_argument('--steps', type=int, default=100)
    ap.add_argument('--batch', type=int, default=8)
    ap.add_argument('--seq-len', type=int, default=64)
    ap.add_argument('--ckpt-every', type=int, default=25)
    ap.add_argument('--log-every', type=int, default=10)
    ap.add_argument('--profile', action='store_true',
                    help='span records (data/dispatch/wait/host per step) '
                         '+ memory/HLO telemetry (repro.obs); the step and '
                         'donation are unchanged')
    ap.add_argument('--head-policy', default='dense',
                    choices=['dense', 'exclude', 'shard'],
                    help='oversized-factor policy (core.factor_sharded): '
                         'dense = legacy, exclude = MKOR-style identity '
                         'guard, shard = matrix-free distributed solve')
    ap.add_argument('--head-threshold', type=int, default=65536,
                    help='factor dim at/above which --head-policy applies '
                         '(vocab-scale factors by default)')
    ap.add_argument('--solve-iters', type=int, default=32,
                    help="iterations of the head-policy='shard' solve")
    ap.add_argument('--kernel-impl', default=None,
                    choices=['auto', 'pallas', 'pallas_interpret', 'xla'],
                    help='kernel dispatch impl for the Eva hot-path ops '
                         '(kernels.dispatch); default: leave the optimizer '
                         'on its own use_pallas behavior')
    ap.add_argument('--autotune', action='store_true',
                    help='benchmark tile/impl candidates for this model\'s '
                         'preconditioned shapes, write the winner cache to '
                         'the run dir and dispatch through it')
    ap.add_argument('--fused', action='store_true',
                    help='fused precondition→update epilogue: one kernel '
                         'launch per bucket for eva/eva_f/eva_s, single-'
                         'traversal elementwise tail for kfac/foof/shampoo')
    ap.add_argument('--out-dir', default='runs/launch')
    ap.add_argument('--no-prefetch', action='store_true')
    ap.add_argument('--distributed', action='store_true',
                    help='call jax.distributed.initialize() (multi-host pods)')
    ap.add_argument('--elastic', action='store_true',
                    help='elastic outer loop (Trainer.fit_elastic): explicit '
                         'DP over --world local devices; checkpoints reshard '
                         'across world sizes (docs/CHECKPOINT_FORMAT.md)')
    ap.add_argument('--world', type=int, default=0,
                    help='data-parallel worker count for --elastic '
                         '(0 = every local device)')
    args = ap.parse_args(argv)

    enable_compile_cache()
    if args.distributed:
        jax.distributed.initialize()

    if args.arch == 'demo':
        cfg = demo_lm('small')
    elif args.arch.startswith('demo-'):
        cfg = demo_lm(args.arch.split('-', 1)[1])
    else:
        cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    if cfg.family in ('encdec', 'vlm') or cfg.input_is_embeds:
        raise SystemExit(f'{cfg.name}: use the dry-run/examples for stub-'
                         'frontend archs; the LM trainer needs token input')

    model = build_model(cfg)
    init = lambda: M.init_params(model.param_specs(), jax.random.PRNGKey(0))
    # shapes only: the arrays are made as they are handed to the trainer,
    # so that its pre-donation copy leaves no second set alive here
    shapes = kvlib.flatten_params(jax.eval_shape(init))
    print(f'{cfg.name}: {M.count_params(model.param_specs())/1e6:.2f}M params')
    stream = LMStream(vocab=cfg.vocab, seq_len=args.seq_len, batch=args.batch,
                      seed=0)
    data = stream if args.no_prefetch else Prefetcher(stream)
    opt_kwargs = {}
    if args.fused:
        opt_kwargs['fused'] = True
    opt, capture = make_optimizer(args.opt, lr=args.lr, **opt_kwargs)
    taps_fn = None
    if capture.b == 'outer':
        # K-FAC-style capture needs full z-shaped taps (kv.make_full_taps);
        # batch-aware so the elastic DP step sizes them to batch/W rows
        paths = set(model.precon_paths()) & set(shapes)
        taps_fn = lambda p, b: kvlib.make_full_taps(p, paths,
                                                    b['tokens'].shape)
    factor = None
    if args.head_policy != 'dense':
        from repro.core.factor_sharded import FactorShardConfig
        factor = FactorShardConfig(head_policy=args.head_policy,
                                   shard_threshold=args.head_threshold,
                                   solve_iters=args.solve_iters)
    tc = TrainerConfig(total_steps=args.steps, log_every=args.log_every,
                       ckpt_every=args.ckpt_every, profile=args.profile,
                       out_dir=f'{args.out_dir}/{cfg.name}-{args.opt}')
    kernel = None
    if args.kernel_impl or args.autotune:
        from repro.kernels import autotune as ktune
        from repro.kernels.dispatch import KernelConfig
        cache_path = None
        if args.autotune:
            # tune the distinct 2-D trailing shapes the preconditioner will
            # actually dispatch (bucketed layers share a shape = one entry)
            tuned = sorted({tuple(int(d) for d in shapes[p].shape[-2:])
                            for p in model.precon_paths()
                            if p in shapes and shapes[p].ndim >= 2})
            print(f'[launch] autotuning {len(tuned)} shapes: {tuned}')
            cache = ktune.tune(tuned)
            cache_path = str(ktune.write(
                cache, f'{tc.out_dir}/tile_cache.json'))
            print(f'[launch] autotune cache -> {cache_path}')
        kernel = KernelConfig(impl=args.kernel_impl or 'auto',
                              autotune_cache=cache_path,
                              autotune=args.autotune)
    trainer = Trainer(model, opt, capture, tc, taps_fn=taps_fn,
                      factor=factor, kernel=kernel)
    try:
        if args.elastic:
            return trainer.fit_elastic(init(), data,
                                       world=args.world or None)[2]
        return trainer.fit(init(), data)[2]
    finally:
        if data is not stream:
            data.close()


if __name__ == '__main__':
    main()
