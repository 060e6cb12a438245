import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e).

For every (architecture × input shape × mesh) cell:
  lower the step function with production shardings on 256-chip single-pod
  and 512-chip multi-pod meshes, ``.compile()`` it, and record
  ``memory_analysis()`` / ``cost_analysis()`` / trip-count-corrected HLO
  costs (FLOPs, HBM traffic, collective bytes) into results/dryrun/*.json.

The first two lines of this file force 512 host platform devices BEFORE any
jax import — nothing else in the repo sets this flag (smoke tests and
benchmarks see the real single CPU device).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                 # all cells
  ... --arch kimi-k2-1t-a32b --shape train_4k --mesh multi     # one cell
  ... --list                                                   # show plan
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, cell_skip_reason, get_config
from repro.configs.registry import ARCH_IDS
from repro.core.registry import make_optimizer
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.models import build_model, decode_specs, prefill_batch_specs, train_batch_specs
from repro.models import module as M
from repro.sharding import (cache_shardings, input_shardings,
                            opt_state_shardings, param_shardings)
from repro.train.step import abstract_opt_state, make_train_step

V5E = dict(peak_flops=197e12, hbm_bw=819e9, ici_bw=50e9)


def active_param_counts(specs) -> tuple[int, int]:
    """(total, active) params; MoE expert weights count at top_k/n_experts."""
    flat = M.flatten_specs(specs)
    total = sum(int(jnp.prod(jnp.array(s.shape))) for s in flat.values())
    return total, total  # corrected by caller for MoE


def model_flop_params(cfg, specs) -> tuple[int, int]:
    import math
    flat = M.flatten_specs(specs)
    total = sum(math.prod(s.shape) for s in flat.values())
    expert = sum(math.prod(s.shape) for p, s in flat.items()
                 if '/moe/' in f'/{p}' and not p.endswith('router/w'))
    if cfg.n_experts:
        active = total - expert + expert * (cfg.top_k / cfg.n_experts)
    else:
        active = total
    return int(total), int(active)


def build_cell(cfg, shape, mesh, fallback_log):
    """Returns (fn, args, in_shardings, donate, tokens_processed)."""
    model = build_model(cfg)
    specs = model.param_specs()
    params_sds = M.abstract_params(specs)
    p_shard = param_shardings(specs, mesh, fallback_log)

    if shape.kind == 'train':
        opt, capture = make_optimizer('eva', lr=0.01)
        batch = train_batch_specs(cfg, shape)
        opt_sds = abstract_opt_state(model, opt, capture, params_sds, batch)
        o_shard = opt_state_shardings(opt_sds, specs, mesh)
        b_shard = input_shardings(batch, mesh)
        fn = make_train_step(model, opt, capture,
                             microbatches=cfg.microbatches)
        tokens = shape.global_batch * shape.seq_len
        return (fn, (params_sds, opt_sds, batch),
                (p_shard, o_shard, b_shard), (0, 1), tokens, 'train')
    if shape.kind == 'prefill':
        batch = prefill_batch_specs(cfg, shape)
        b_shard = input_shardings(batch, mesh)
        fn = model.prefill_fn
        tokens = shape.global_batch * shape.seq_len
        return fn, (params_sds, batch), (p_shard, b_shard), (), tokens, 'prefill'
    # decode
    cache_sds, tok_sds, pos_sds = decode_specs(cfg, shape)
    c_shard = cache_shardings(cache_sds, mesh)
    t_shard = input_shardings(tok_sds, mesh, seq_dim=None)
    pos_shard = input_shardings(pos_sds, mesh, seq_dim=None)
    fn = model.decode_fn
    tokens = shape.global_batch  # one new token per sequence
    return (fn, (params_sds, cache_sds, tok_sds, pos_sds),
            (p_shard, c_shard, t_shard, pos_shard), (1,), tokens, 'decode')


def run_cell(arch_id: str, shape, multi_pod: bool, out_dir: Path,
             force: bool = False) -> dict:
    mesh_name = 'multi' if multi_pod else 'single'
    out_path = out_dir / f'{arch_id}__{shape.name}__{mesh_name}.json'
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())

    cfg = get_config(arch_id)
    skip = cell_skip_reason(cfg, shape)
    rec = {'arch': arch_id, 'shape': shape.name, 'mesh': mesh_name,
           'seq_len': shape.seq_len, 'global_batch': shape.global_batch,
           'kind': shape.kind}
    if skip:
        rec['skipped'] = skip
        out_path.write_text(json.dumps(rec, indent=1))
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = len(mesh.devices.reshape(-1))
    fallback_log: list = []
    t0 = time.time()
    fn, args, shardings, donate, tokens, kind = build_cell(cfg, shape, mesh,
                                                           fallback_log)
    with jax.set_mesh(mesh):
        lowered = jax.jit(fn, in_shardings=shardings,
                          donate_argnums=donate).lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    hlo = hlo_analysis.analyze(compiled.as_text())

    specs = build_model(cfg).param_specs()
    total_p, active_p = model_flop_params(cfg, specs)
    if kind == 'train':
        model_flops = 6.0 * active_p * tokens
    else:
        model_flops = 2.0 * active_p * tokens

    per_dev = dict(
        hlo_flops=hlo.flops,
        hbm_traffic_bytes=hlo.traffic_bytes,
        collective_bytes=hlo.collective_bytes,
        cost_analysis_flops=float(ca.get('flops', 0.0)),
        cost_analysis_bytes=float(ca.get('bytes accessed', 0.0)),
    )
    roofline = dict(
        compute_s=hlo.flops / V5E['peak_flops'],
        memory_s=hlo.traffic_bytes / V5E['hbm_bw'],
        collective_s=hlo.collective_bytes / V5E['ici_bw'],
    )
    dominant = max(roofline, key=roofline.get)
    rec.update(
        n_chips=n_chips,
        params_total=total_p, params_active=active_p,
        tokens_per_step=tokens,
        model_flops_total=model_flops,
        model_flops_per_chip=model_flops / n_chips,
        useful_flop_ratio=(model_flops / n_chips) / max(hlo.flops, 1.0),
        per_device=per_dev,
        roofline_s=roofline,
        dominant=dominant,
        collective_by_op=hlo.collective_by_op,
        collective_count=hlo.collective_count,
        memory=dict(
            argument_bytes=mem.argument_size_in_bytes,
            output_bytes=mem.output_size_in_bytes,
            temp_bytes=mem.temp_size_in_bytes,
            alias_bytes=mem.alias_size_in_bytes,
            total_bytes=(mem.argument_size_in_bytes + mem.temp_size_in_bytes
                         + mem.output_size_in_bytes - mem.alias_size_in_bytes),
        ),
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        sharding_fallbacks=sorted(set(fallback_log)),
    )
    out_path.write_text(json.dumps(rec, indent=1))
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument('--arch', default=None)
    ap.add_argument('--shape', default=None)
    ap.add_argument('--mesh', default='both', choices=['single', 'multi', 'both'])
    ap.add_argument('--out', default='results/dryrun')
    ap.add_argument('--force', action='store_true')
    ap.add_argument('--list', action='store_true')
    args = ap.parse_args()

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    archs = [args.arch] if args.arch else list(ARCH_IDS)
    shapes = [s for s in SHAPES if args.shape in (None, s.name)]
    meshes = {'single': [False], 'multi': [True], 'both': [False, True]}[args.mesh]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f'{arch} × {shape.name} × {"multi" if mp else "single"}'
                if args.list:
                    print(tag)
                    continue
                try:
                    rec = run_cell(arch, shape, mp, out_dir, force=args.force)
                    if 'skipped' in rec:
                        print(f'SKIP  {tag}: {rec["skipped"]}')
                    else:
                        r = rec['roofline_s']
                        print(f'OK    {tag}: compile={rec["compile_s"]}s '
                              f'mem={rec["memory"]["total_bytes"]/2**30:.2f}GiB/dev '
                              f'compute={r["compute_s"]*1e3:.1f}ms '
                              f'mem_t={r["memory_s"]*1e3:.1f}ms '
                              f'coll={r["collective_s"]*1e3:.1f}ms '
                              f'dom={rec["dominant"]}')
                except Exception as e:  # noqa: BLE001 — record and continue
                    failures.append((tag, repr(e)))
                    print(f'FAIL  {tag}: {e!r}')
                    traceback.print_exc()
    if failures:
        raise SystemExit(f'{len(failures)} cells failed: '
                         + '; '.join(t for t, _ in failures))


if __name__ == '__main__':
    main()
