"""Exchange layer (repro.comm.exchange): owned-slice refresh equivalence,
traffic accounting, and config plumbing.

Contracts proven here:
  * the owned-slice gather refresh exchange (``exchange='gather'``, the
    default) is BIT-exact (atol=0) against the legacy full-stack
    zero-padded psum for ALL SIX optimizers on a 4-device host mesh (f32
    codec), state included — and within 1e-2 relative under the int8
    codec;
  * raw gather reconstruction is atol=0 for the identity codec and for
    bf16-of-bf16-representable state;
  * ``topology='pod'`` (pod-local ownership, intra-pod ICI slice gather +
    one cross-pod zero-padded bucket psum) is atol=0 vs psum on a (2,2)
    ('pod','data') mesh, and the assignment keeps every bucket inside one
    pod with balanced intra-pod counts;
  * the int8 gradient all-reduce under shard_map matches the historical
    ``quantize_allreduce`` semantics and reports zero saturation;
  * at W=4 the owned-slice exchange moves ≥2× fewer logical bytes than the
    full-stack psum on the qwen2-0.5b bucket structure (the acceptance
    number ``benchmarks/roofline.py`` records);
  * the static gather maps cover every stack row exactly once and pad to
    the max per-worker count;
  * ``Extras.comm`` threads the config end to end.
"""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import exchange, metrics
from repro.comm.codec import F32, INT8_EF
from repro.core import bucketing
from repro.core.transform import Extras
from repro.schedule import ownership


# ---------------------------------------------------------------------------
# Static gather maps


def test_gather_maps_cover_and_pad():
    owner = (0, 1, 2, 3, 0, 0)            # worker 0 owns 3 items
    send, src, m = exchange._gather_maps(owner, 4)
    assert m == 3 and send.shape == (4, 3) and src.shape == (6,)
    # every worker's row lists its owned items (padded by repetition)
    assert set(send[0]) == {0, 4, 5}
    assert set(send[1]) == {1} and set(send[2]) == {2} and set(send[3]) == {3}
    # src recovers each item from its owner's slot, all distinct
    flat = np.full(4 * m, -1, np.int64)
    for w in range(4):
        for j, i in enumerate(send[w]):
            if flat[w * m + j] == -1:
                flat[w * m + j] = i
    recovered = flat[src]
    np.testing.assert_array_equal(recovered, np.arange(6))


def test_gather_maps_idle_worker():
    send, src, m = exchange._gather_maps((0, 0), 4)   # workers 1-3 idle
    assert m == 2
    np.testing.assert_array_equal(src, [0, 1])
    assert (send[1:] == 0).all()          # idle workers send padding


def test_pod_slice_owners_stay_pod_local():
    """topology='pod': every bucket's slices are owned inside ONE pod, the
    intra-pod counts are balanced, and the map is deterministic."""
    flat = {f'b{i}/w': jnp.zeros((8, 4)) for i in range(5)}
    flat['stack/w'] = jnp.zeros((6, 8, 4))
    plan = bucketing.build_plan(flat)
    cost = ownership.inverse_cost('both')
    own = ownership.assign_pod_slice_owners(plan, cost, (2, 2))
    used_pods = set()
    for b in plan.buckets:
        o = own[b.key]
        assert o.shape == (len(b.paths) * ownership.lead_size(b),)
        pods = {int(w) // 2 for w in o}
        assert len(pods) == 1, (b.key, o)          # pod-local
        used_pods |= pods
        counts = np.bincount(np.asarray(o) % 2, minlength=2)
        assert counts.max() - counts.min() <= 1    # intra-pod balance
    assert used_pods == {0, 1}                     # buckets LPT over pods
    again = ownership.assign_pod_slice_owners(plan, cost, (2, 2))
    for k in own:
        np.testing.assert_array_equal(own[k], again[k])


# ---------------------------------------------------------------------------
# Config plumbing


def test_exchange_config_defaults_and_validation():
    cfg = exchange.ExchangeConfig()
    assert cfg.exchange == 'gather' and cfg.grads == 'int8'
    assert cfg.stats == 'f32' and cfg.codec == 'f32'
    with pytest.raises(ValueError):
        exchange.ExchangeConfig(exchange='broadcast')


def test_from_extras():
    assert exchange.from_extras(None) == exchange.ExchangeConfig()
    assert exchange.from_extras(Extras()) == exchange.ExchangeConfig()
    cfg = exchange.ExchangeConfig(codec='int8', exchange='psum')
    assert exchange.from_extras(Extras(comm=cfg)) is cfg


def test_pmean_stats_codec_noop_outside_mesh():
    from repro.sharding.constraints import pmean_stats
    tree = {'s': jnp.ones((3, 3))}
    for codec in (None, 'f32', 'bf16', 'int8'):
        out = pmean_stats(tree, codec=codec)
        np.testing.assert_array_equal(np.asarray(out['s']),
                                      np.asarray(tree['s']))
    assert pmean_stats(None, codec='int8') is None


# ---------------------------------------------------------------------------
# Traffic accounting: the W=4 acceptance number on the real bucket structure


def _qwen_inverse_stacks():
    """The slice-granular cached-inverse stacks of qwen2-0.5b, shapes only
    — (N·lead, d, d) per side, mirroring what ``sharded_refresh``
    exchanges."""
    from repro.configs.registry import get_config
    from repro.models import build_model
    from repro.models import module as M

    cfg = get_config('qwen2-0.5b')
    model = build_model(cfg)
    specs = M.flatten_specs(model.param_specs())
    precon = {p: specs[p] for p in sorted(set(model.precon_paths()) & set(specs))}
    plan = bucketing.build_plan(precon)
    return plan, exchange.slice_stack_specs(plan, 'both')


def test_owned_slice_bytes_at_w4_at_least_2x_smaller():
    plan, stacks = _qwen_inverse_stacks()
    world = 4
    owners = ownership.assign_slice_owners(plan,
                                           ownership.inverse_cost('both'),
                                           world)
    psum_b = exchange.refresh_exchange_bytes(plan, owners, stacks, world,
                                             mode='psum')
    ag_b = exchange.refresh_exchange_bytes(plan, owners, stacks, world,
                                           codec='f32', mode='gather')
    assert psum_b > 0 and ag_b > 0
    ratio = psum_b / ag_b
    assert ratio >= 2.0, (psum_b, ag_b, ratio)
    # int8 refresh wire shrinks it ~4x further
    ag_i8 = exchange.refresh_exchange_bytes(plan, owners, stacks, world,
                                            codec='int8', mode='gather')
    assert psum_b / ag_i8 >= 2.0 * 3.5


def test_owned_slice_bytes_padding_counted():
    """3 equal items over 2 workers: M=2, so the all-gather still moves
    2/3 of the stack per worker (padding is not free) — the accounting
    must say so rather than the idealized 1/W."""
    plan = bucketing.build_plan({f'l{i}/w': jnp.zeros((4, 4)) for i in range(3)})
    owners = {plan.buckets[0].key: np.array([0, 1, 0])}
    stacks = {plan.buckets[0].key: jax.ShapeDtypeStruct((3, 4, 4), jnp.float32)}
    ag = exchange.refresh_exchange_bytes(plan, owners, stacks, 2,
                                         codec='f32', mode='gather')
    assert ag == 2 * 4 * 4 * 4            # M=2 rows of 4x4 f32
    ps = exchange.refresh_exchange_bytes(plan, owners, stacks, 2, mode='psum')
    assert ps == 3 * 4 * 4 * 4


# ---------------------------------------------------------------------------
# 4-device equivalence: psum vs owned-slice all-gather for all six methods
# (subprocess: the forced 4-device flag must not leak into this process)

_EQUIV_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.comm import metrics
    from repro.comm.exchange import (ExchangeConfig, allgather_owned_slices,
                                     allreduce_mean_tree)
    from repro.core import bucketing
    from repro.schedule import ownership
    from repro.core import kv as kvlib
    from repro.core.eva import eva_preconditioner
    from repro.core.eva_f import eva_f_preconditioner
    from repro.core.eva_s import eva_s_preconditioner
    from repro.core.foof import foof_preconditioner
    from repro.core.kfac import kfac_preconditioner
    from repro.core.shampoo import shampoo_preconditioner
    from repro.core.transform import Extras
    from repro.schedule.policy import every_k
    from repro.schedule.runtime import RefreshRuntime
    from repro.launch.mesh import make_mesh

    SHAPES = {'blk0/w': (8, 4), 'blk1/w': (8, 4), 'blk2/w': (8, 4),
              'head/w': (8, 3), 'stack/w': (2, 6, 4)}

    def psd(key, *shape):
        m = jax.random.normal(key, shape)
        return m @ jnp.swapaxes(m, -1, -2) + 0.1 * jnp.eye(shape[-1])

    def grads(seed):
        key = jax.random.PRNGKey(seed)
        return {p: jax.random.normal(jax.random.fold_in(key, i), s)
                for i, (p, s) in enumerate(SHAPES.items())}

    def stats(seed):
        key = jax.random.PRNGKey(1000 + seed)
        out = {}
        for i, (p, s) in enumerate(SHAPES.items()):
            ks = jax.random.split(jax.random.fold_in(key, i), 4)
            lead, d_in, d_out = s[:-2], s[-2], s[-1]
            out[p] = kvlib.LayerStats(
                a_mean=jax.random.normal(ks[0], lead + (d_in,)),
                b_mean=jax.random.normal(ks[1], lead + (d_out,)),
                a_outer=psd(ks[2], *lead, d_in, d_in),
                b_outer=psd(ks[3], *lead, d_out, d_out))
        return out

    MAKERS = {
        'eva': lambda: eva_preconditioner(0.03, 0.9, policy=every_k(2)),
        'eva_f': lambda: eva_f_preconditioner(0.03, 0.9, policy=every_k(2)),
        'eva_s': lambda: eva_s_preconditioner(0.03, 0.9, policy=every_k(2)),
        'foof': lambda: foof_preconditioner(0.03, 0.9, policy=every_k(2)),
        'kfac': lambda: kfac_preconditioner(0.03, 0.9, policy=every_k(2)),
        'shampoo': lambda: shampoo_preconditioner(1e-4, policy=every_k(2)),
    }
    NEEDS_STATS = {'eva', 'eva_f', 'foof', 'kfac'}
    STEPS = 3
    mesh = make_mesh((4,), ('data',))
    params = kvlib.unflatten_params(grads(0))

    def run(method, comm, states=None):
        opt = MAKERS[method]()
        rt = RefreshRuntime(shard_refresh=True)
        ex = lambda t: (Extras(stats=stats(t), sched=rt, comm=comm)
                        if method in NEEDS_STATS
                        else Extras(sched=rt, comm=comm))
        state = opt.init(params, ex(0))

        def body(g, s, st):
            e = (Extras(stats=st, sched=rt, comm=comm)
                 if method in NEEDS_STATS else Extras(sched=rt, comm=comm))
            return opt.update(g, s, extras=e)

        in_specs = (P(), P(), P()) if method in NEEDS_STATS else (P(), P())
        step = jax.jit(jax.shard_map(
            (body if method in NEEDS_STATS
             else (lambda g, s: body(g, s, None))),
            mesh=mesh, in_specs=in_specs, out_specs=(P(), P()),
            check_vma=False))
        outs = []
        for t in range(STEPS):
            args = (grads(t), state, stats(t)) if method in NEEDS_STATS \
                else (grads(t), state)
            out, state = step(*args)
            outs.append(out)
            if states is not None:
                states.append(state)
        return outs, state

    def maxdiff(a, b):
        return max(float(np.max(np.abs(
            np.asarray(x).astype(np.float64) -
            np.asarray(y).astype(np.float64))))
            for x, y in zip(jax.tree_util.tree_leaves(a),
                            jax.tree_util.tree_leaves(b)))

    def maxabs(a):
        return max(float(np.max(np.abs(np.asarray(x))))
                   for x in jax.tree_util.tree_leaves(a))

    def shampoo_int8_bound(states):
        # shampoo applies out = P_in G P_out with the exchanged inverse
        # roots.  The int8 wire carries one max-scale per stack row, so each
        # root entry lands within half a quantization step of its row,
        # |E| <= d = max|P_row| / 254, and elementwise
        #   |dout| <= d_in 1|G||P_out| + |P_in||G|1 d_out + d_in d_out 1|G|1
        # (1 = all-ones).  The roots used at step t are those in the exact
        # (psum) run's state after step t.
        from repro.core.eva_s import default_precon_predicate
        worst = 0.0
        for t, st in enumerate(states):
            flat = grads(t)
            plan = bucketing.build_plan(flat, default_precon_predicate)
            for b in plan.buckets:
                for i, path in enumerate(b.paths):
                    pi = jnp.abs(st.p_in[b.key][i])
                    po = jnp.abs(st.p_out[b.key][i])
                    g = jnp.abs(flat[path])
                    d_in, d_out = jnp.max(pi) / 254.0, jnp.max(po) / 254.0
                    bound = (d_in * (jnp.sum(g, -2, keepdims=True) @ po)
                             + d_out * (pi @ jnp.sum(g, -1, keepdims=True))
                             + d_in * d_out * jnp.sum(g, (-2, -1),
                                                      keepdims=True))
                    worst = max(worst, float(jnp.max(bound)))
        return worst

    rec = {'devices': jax.device_count(), 'methods': {}}
    for method in sorted(MAKERS):
        ps_states = []
        o_ps, s_ps = run(method, ExchangeConfig(exchange='psum'), ps_states)
        o_ag, s_ag = run(method, ExchangeConfig(exchange='gather'))
        o_i8, s_i8 = run(method, ExchangeConfig(exchange='gather',
                                                codec='int8'))
        scale = max(maxabs(o_ps), 1e-12)
        rec['methods'][method] = {
            'ag_vs_psum_out': maxdiff(o_ag, o_ps),
            'ag_vs_psum_state': maxdiff(s_ag, s_ps),
            'int8_vs_psum_rel': maxdiff(o_i8, o_ps) / scale,
        }
        if method == 'shampoo':
            rec['methods'][method]['int8_bound_rel'] = \
                shampoo_int8_bound(ps_states) / scale

    # int8 gradient all-reduce under shard_map: mean within half a step of
    # exact, saturation identically zero
    g = grads(7)
    err0 = jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, jnp.float32), g)

    def reduce_body(gs, es):
        return allreduce_mean_tree(gs, es, codec='int8', axes=('data',),
                                   site='grads/test')

    red = jax.jit(jax.shard_map(
        reduce_body, mesh=mesh, in_specs=(P(), P()),
        out_specs=(P(), P(), P()), check_vma=False))
    mean, new_err, info = red(g, err0)
    exact = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), g)
    rec['grad_int8_err'] = maxdiff(mean, exact)
    rec['grad_int8_scale'] = max(
        float(jnp.max(jnp.abs(x))) / 127.0
        for x in jax.tree_util.tree_leaves(g))
    rec['saturation'] = float(info['saturation'])

    # --- raw owned-slice gather: identity and bf16-of-bf16 are atol=0 ---
    flatg = {f'l{i}/w': jax.random.normal(jax.random.PRNGKey(i), (4, 4))
             for i in range(6)}
    plan2 = bucketing.build_plan(flatg)
    key2 = plan2.buckets[0].key
    stack = jnp.stack([flatg[p] for p in plan2.buckets[0].paths])
    owners2 = ownership.assign_slice_owners(plan2,
                                            ownership.inverse_cost('both'), 4)

    def gather_of(codec):
        def body(s):
            w, r = ownership.world_and_rank(('data',))
            out = allgather_owned_slices(plan2, owners2, w, r, {key2: s},
                                         codec=codec, axes=('data',))
            return out[key2]
        return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(),),
                                     out_specs=P(), check_vma=False))

    # every worker holds the full true stack; non-owned rows are never read,
    # so the reconstruction must equal the input exactly
    rec['gather_identity_err'] = maxdiff(gather_of('identity')(stack), stack)
    stack_bf = stack.astype(jnp.bfloat16).astype(jnp.float32)
    rec['gather_bf16_of_bf16_err'] = maxdiff(gather_of('bf16')(stack_bf),
                                             stack_bf)

    # --- topology='pod' on a (2,2) ('pod','data') mesh: the two-stage
    # (ICI slice gather + DCN bucket psum) exchange ≡ full-stack psum ---
    mesh22 = make_mesh((2, 2), ('pod', 'data'))

    def run22(method, comm):
        opt = MAKERS[method]()
        rt = RefreshRuntime(shard_refresh=True)
        state = opt.init(params, Extras(stats=stats(0), sched=rt, comm=comm))

        def body(g, s, st):
            return opt.update(g, s, extras=Extras(stats=st, sched=rt,
                                                  comm=comm))

        step = jax.jit(jax.shard_map(
            body, mesh=mesh22, in_specs=(P(), P(), P()),
            out_specs=(P(), P()), check_vma=False))
        outs = []
        for t in range(STEPS):
            out, state = step(grads(t), state, stats(t))
            outs.append(out)
        return outs, state

    o22_ps, s22_ps = run22('kfac', ExchangeConfig(exchange='psum'))
    o22_pod, s22_pod = run22('kfac', ExchangeConfig(exchange='gather',
                                                    topology='pod'))
    rec['pod_vs_psum_out'] = maxdiff(o22_pod, o22_ps)
    rec['pod_vs_psum_state'] = maxdiff(s22_pod, s22_ps)

    rec['sites'] = {k: {kk: vv for kk, vv in v.items() if kk != 'traces'}
                    for k, v in metrics.snapshot().items()}
    print(json.dumps(rec))
""")


@pytest.mark.multihost
def test_owned_slice_exchange_matches_psum_all_methods():
    out = subprocess.run(
        [sys.executable, '-c', _EQUIV_SCRIPT],
        capture_output=True, text=True, timeout=1800,
        # JAX_PLATFORMS pinned: the scrubbed env must not fall through to
        # accelerator discovery (libtpu-on-a-TPU-less-host hangs forever)
        env={'PYTHONPATH': 'src', 'PATH': '/usr/bin:/bin', 'HOME': '/root',
             'JAX_PLATFORMS': 'cpu'},
        cwd=Path(__file__).resolve().parent.parent)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec['devices'] == 4
    for method, r in rec['methods'].items():
        # owned-slice all-gather ≡ full-stack psum, bit-exact, state included
        assert r['ag_vs_psum_out'] == 0.0, (method, r)
        assert r['ag_vs_psum_state'] == 0.0, (method, r)
        # int8 refresh wire: shampoo's two-sided root product is held to
        # the bound its per-row quantization step implies (computed in the
        # script, see shampoo_int8_bound); the others to 1e-2 relative of
        # the exact exchange
        bound = r.get('int8_bound_rel', 1e-2)
        assert r['int8_vs_psum_rel'] <= bound, (method, r)
    # replicated inputs: the int8+EF mean must sit within half a
    # quantization step of the exact value, with zero saturation
    assert rec['grad_int8_err'] <= 0.5 * rec['grad_int8_scale'] + 1e-7
    assert rec['saturation'] == 0.0
    # raw gather reconstruction: the identity codec and bf16-of-bf16-
    # representable values round-trip the stack bit-exactly (the ISSUE's
    # atol=0 contract for the default exchange='gather')
    assert rec['gather_identity_err'] == 0.0
    assert rec['gather_bf16_of_bf16_err'] == 0.0
    # topology='pod' two-stage exchange (ICI slice gather + one DCN
    # zero-padded bucket psum) is exact too
    assert rec['pod_vs_psum_out'] == 0.0
    assert rec['pod_vs_psum_state'] == 0.0
    # the byte counters saw the refresh call-sites with the gather mode.
    # Exactly the three inverse-caching methods exchange — for the eva
    # family the refresh is a snapshot select with NO exchange, so their
    # psum≡allgather rows above are no-op coverage, not proof; this
    # assertion is what keeps the "all six" claim honest (a future
    # eva-family cached path would show up here and demand real proof).
    sites = rec['sites']
    assert sites['grads/test']['codec'] == 'int8'
    refresh_sites = {s for s in sites if s.startswith('refresh/')}
    assert refresh_sites == {'refresh/kfac', 'refresh/foof',
                             'refresh/shampoo'}, refresh_sites
    assert all(sites[s]['mode'] in ('gather', 'gather-pod')
               for s in refresh_sites)
    # the last-traced kfac cell ran pod topology: the record carries the
    # ICI/DCN byte split of the two-stage exchange
    kf = sites['refresh/kfac']
    assert kf['mode'] == 'gather-pod' and kf['pods'] == [2, 2]
    assert kf['ici_bytes'] > 0 and kf['dcn_bytes'] > 0
    assert kf['bytes_per_call'] == kf['ici_bytes'] + kf['dcn_bytes']
