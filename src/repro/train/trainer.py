"""Training loop with fault tolerance (deliverable: large-scale runnability).

Features:
  * jit'd train step with donated params/opt-state,
  * deterministic seekable data (resume is bit-exact),
  * async checkpointing every ``ckpt_every`` steps + keep-K GC,
  * preemption handling: SIGTERM/SIGINT → synchronous checkpoint → clean
    exit (the standard TPU-pod eviction contract),
  * straggler watchdog (``repro.obs.spans.StragglerWatchdog``): steps
    slower than ``straggler_factor``× the running median emit a typed
    ``straggler`` record (on a real pod this feeds the controller that
    evicts/replaces the slow host),
  * unified telemetry (``repro.obs``): every record in ``metrics.jsonl``
    is schema-typed and versioned; comm-site attribution uses the
    recorder's run-scoped counter context instead of baselining the
    process-global table,
  * one-step-lagged read-back: ``fit`` dispatches step n+1 before it
    reads step n's scalars back (one ``device_get`` of a small dict, the
    loop's one sync point), so the device runs back to back and host
    work shorter than a step hides behind the next step; it drains first
    only where the host needs step n's state (see :meth:`Trainer.fit`),
  * named phases on the profiler's trace: each step is a
    ``StepTraceAnnotation('train')`` holding host spans ``train.data``
    (``batch_at``), ``train.dispatch`` (the jitted call), ``train.wait``
    (the read-back) and ``train.host`` (the rest) -- in ``fit`` the wait
    and host of the step before it; the step's device ops carry the
    ``jax.named_scope`` names of ``train/step.py``.  Both cost nothing
    without a running profiler, and neither waits on the device,
  * ``profile`` mode: the same donated step and loop, plus a ``span``
    record per span, per-step live-buffer samples and a one-shot HLO cost
    record of the compiled step.

Elasticity: checkpoints are world-agnostic (full logical arrays + the
``elastic`` metadata block — see docs/CHECKPOINT_FORMAT.md for the on-disk
contract and the W-resharding semantics).  ``fit_elastic`` is the elastic
outer loop: it restores a checkpoint written at any world size, reshards
it through ``repro.schedule.reshard`` (re-derives ownership for the new W,
drains in-flight pipeline buffers), rebuilds the data mesh, re-jits and
continues — and tolerates live worker-count changes *between* steps the
same way, emitting a typed ``reshard`` event per resize.
"""
from __future__ import annotations

import dataclasses
import time
from pathlib import Path
from typing import Any, Callable, Optional

import jax

from repro.core import kv as kvlib
from repro.core.transform import GradientTransformation
from repro.models import flash as attn
from repro.obs import events as obs_events
from repro.obs import spans as obs_spans
from repro.schedule import reshard as reshard_mod
from repro.schedule import runtime as schedrt
from repro.train import checkpoint as ckpt
from repro.train.step import (init_opt_state, make_dp_step,
                              make_train_step, stats_plan_of)


@dataclasses.dataclass
class TrainerConfig:
    total_steps: int = 100
    log_every: int = 10
    ckpt_every: int = 0            # 0 = no checkpointing
    keep_ckpts: int = 3
    out_dir: str = 'runs/default'
    straggler_factor: float = 3.0
    donate: bool = True
    profile: bool = False          # span records + memory/HLO samples


class Trainer:
    def __init__(self, model, opt: GradientTransformation,
                 capture: kvlib.CaptureConfig, cfg: TrainerConfig,
                 taps_fn: Optional[Callable] = None,
                 sched: Optional[schedrt.RefreshRuntime] = None,
                 comm=None, factor=None, kernel=None):
        self.model = model
        self.opt = opt
        self.capture = capture
        self.cfg = cfg
        self.taps_fn = taps_fn
        self.sched = sched if sched is not None else schedrt.RefreshRuntime()
        self.comm = comm
        # per-factor oversized-Kronecker policy (core.factor_sharded);
        # None = every factor dense, the bit-exact legacy path
        self.factor = factor
        # ``kernel`` (a kernels.dispatch.KernelConfig) selects nothing: the
        # backend picks the kernel.  It is accepted because
        # bench/harness.py passes the one its traffic files name.
        del kernel
        self.out_dir = Path(cfg.out_dir)
        self.out_dir.mkdir(parents=True, exist_ok=True)
        self.ckpt_dir = self.out_dir / 'ckpt'
        self._ckptr = ckpt.AsyncCheckpointer(self.ckpt_dir, cfg.keep_ckpts)
        step_fn = make_train_step(model, opt, capture, taps_fn=taps_fn,
                                  sched=self.sched, comm=comm, factor=factor)
        self.step_fn = jax.jit(step_fn,
                               donate_argnums=(0, 1) if cfg.donate else ())
        self._watchdog = obs_spans.StragglerWatchdog(cfg.straggler_factor)
        self._preempted = False
        self.metrics_path = self.out_dir / 'metrics.jsonl'

    # -- refresh-runtime observability ---------------------------------------

    def _log_plan(self, recorder, params, batch) -> None:
        """Two startup records: the optimizer's bucket plan, and the
        per-bucket refresh-owner map a W-worker data-parallel run of this
        model would use (W = local device count).  Cheap (eval_shape
        only); None plan (first-order) logs nothing."""
        plan = stats_plan_of(self.model, self.capture, params, batch,
                             taps_fn=self.taps_fn)
        body = schedrt.ownership_event(plan)
        if body is None:
            return
        recorder.emit('optimizer_plan', **obs_events.plan_fields(plan))
        recorder.emit('refresh_ownership', **body)
        print(f"[trainer] refresh ownership over W={body['world']}: "
              + ' '.join(f'{k}:{v}' for k, v in body['owners'].items()),
              flush=True)

    @staticmethod
    def _log_attention(recorder, plans) -> None:
        """One ``attention_plan`` record for a trace of the step: the path
        each attention call site took (nothing when the dispatch traced no
        attention, as a compiled step's does not)."""
        if not plans:
            return
        body = obs_events.attention_fields(plans)
        recorder.emit('attention_plan', **body)
        print('[trainer] attention: ' + ' '.join(
            f"{site}:{p['path']}/{p['block_q']}x{p['block_k']}"
            f"/{p['tiles']}of{p['tiles_total']}"
            for site, p in sorted(body['sites'].items())), flush=True)

    def _log_comm(self, recorder, sites) -> None:
        """One record after the step is traced: the per-call-site logical
        exchange bytes the ``repro.comm`` layer counted for THIS trainer's
        step (empty when nothing in this run exchanges — e.g. single-host
        pjit)."""
        if not sites:
            return
        recorder.emit('comm_exchange', sites=sites)
        print('[trainer] comm exchange: ' + ' '.join(
            f"{s}:{v['bytes_per_call']}B/{v['codec']}/{v['mode']}"
            for s, v in sorted(sites.items())), flush=True)

    # -- preemption ---------------------------------------------------------

    def _install_signal_handlers(self):
        import signal

        def handler(signum, frame):
            del frame
            print(f'[trainer] caught signal {signum}: checkpoint-and-exit '
                  f'requested', flush=True)
            self._preempted = True

        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                signal.signal(sig, handler)
            except ValueError:
                pass  # not in main thread (tests)

    # -- one synchronous step (fit_elastic) ----------------------------------

    @staticmethod
    def _run_step(tracker, step_fn, data, step, params, opt_state,
                  check=None):
        """Fetch, dispatch and read back one step under the ``data``,
        ``dispatch`` and ``wait`` spans, nothing queued behind it.  ``dt``
        runs from the dispatch to the loss on the host, as the watchdog
        and the step record count it."""
        with tracker.span('data', step):
            batch = data.batch_at(step)
            if check is not None:
                check(batch)
        t0 = time.perf_counter()
        with tracker.span('dispatch', step):
            params, opt_state, metrics = step_fn(params, opt_state, batch)
        with tracker.span('wait', step):
            loss = float(metrics['loss'])  # sync point
        return params, opt_state, metrics, batch, loss, \
            time.perf_counter() - t0

    def _emit_profile(self, recorder, step, step_fn, args, one_shot_hlo):
        rec: dict[str, Any] = {'step': step,
                               'live_buffer_mb': obs_spans.live_buffer_mb()}
        dev = obs_spans.device_bytes_in_use()
        if dev is not None:
            rec['device_bytes_in_use'] = dev
        if one_shot_hlo:
            rec['fns'] = {'train_step':
                          obs_spans.compiled_fn_costs(step_fn, *args)}
        recorder.emit('profile', **rec)

    # -- main loop ------------------------------------------------------------

    def fit(self, params, data: Any, start_step: int = 0,
            opt_state=None, resume: bool = True):
        """Train from ``start_step`` to ``cfg.total_steps``; ``data`` must
        expose ``batch_at(step)`` (seekable).

        The loop reads each step back one step late: it fetches and
        dispatches step n+1, then reads step n's scalars (the loss, the
        scheduler fields, ``grad_norm`` on log steps) in one
        ``jax.device_get`` and does step n's host work (watchdog, history,
        records), while step n+1 runs.  It reads step n back before
        dispatching n+1 -- a *drain* -- where the host needs step n's
        state or no step follows: a checkpoint boundary, a preemption
        flag, a ``profile``-mode log step, the last step.  ``history``
        and every record are those of a synchronous loop; the jitted step
        and its donation are the same.

        ``dt`` (the watchdog's and ``step_time_s``) runs from the previous
        read-back to this one, or from the step's dispatch where that is
        later (the first step, and the first after a drain).  On the
        profiler's trace the ``train`` annotation of step n holds
        ``train.data`` and ``train.dispatch`` of step n, then
        ``train.wait`` and ``train.host`` of step n-1; a drained step's
        wait and host lie in a ``drain`` annotation after it.  Each span
        carries its own ``step``.  Before the loop, one ``optimizer_plan``
        record per call lists the optimizer's buckets; a dispatch that
        traces the step writes one ``attention_plan`` record; after it, one
        ``loop`` record counts the read-backs made with the next step
        queued (``overlapped``) and with nothing queued (``drained``).
        """
        cfg = self.cfg
        self._install_signal_handlers()

        if resume and cfg.ckpt_every:
            latest = ckpt.latest_step(self.ckpt_dir)
            if latest is not None:
                template = {'params': params,
                            'opt_state': opt_state if opt_state is not None
                            else init_opt_state(self.model, self.opt,
                                                self.capture, params,
                                                data.batch_at(0),
                                                taps_fn=self.taps_fn,
                                                sched=self.sched,
                                                comm=self.comm,
                                                factor=self.factor)}
                state, meta = ckpt.restore(self.ckpt_dir, latest, template)
                params, opt_state = state['params'], state['opt_state']
                start_step = meta.get('next_step', latest)
                print(f'[trainer] resumed from step {latest}', flush=True)

        if opt_state is None:
            opt_state = init_opt_state(self.model, self.opt, self.capture,
                                       params, data.batch_at(start_step),
                                       taps_fn=self.taps_fn, sched=self.sched,
                                       comm=self.comm, factor=self.factor)

        # refresh count already in the (possibly restored) state — the
        # cumulative exchanged-bytes estimate below must count only THIS
        # run's refreshes, like it counts only this run's steps
        base_sched = schedrt.schedule_metrics(opt_state)
        ref_base = int(base_sched['refreshes']) if base_sched else 0

        if cfg.donate:
            # the jitted step donates its inputs; don't delete caller-owned
            # buffers (callers may reuse the initial params across runs)
            params = jax.tree_util.tree_map(
                lambda x: x + 0 if hasattr(x, 'dtype') else x, params)
            opt_state = jax.tree_util.tree_map(
                lambda x: x + 0 if hasattr(x, 'dtype') else x, opt_state)

        # The recorder owns this run's comm-counter scope: sites traced
        # while it is open belong to THIS fit (a warm-jit second fit
        # re-traces nothing → fall back to the previous fit's sites).
        recorder = obs_events.Recorder(self.metrics_path)
        self._watchdog.recorder = recorder
        tracker = obs_spans.SpanTracker(recorder if cfg.profile else None)
        self._log_plan(recorder, params, data.batch_at(start_step))
        history = []
        prev_ref = ref_base
        last_read = 0.0                 # clock of the previous read-back
        counts = {'overlapped': 0, 'drained': 0}

        def is_log(step):
            return step % cfg.log_every == 0 or step == cfg.total_steps - 1

        def must_drain(step):
            """Read ``step`` back before the next dispatch: the host needs
            its state (a checkpoint, a preemption, a profile sample) or no
            step follows."""
            return bool(step == cfg.total_steps - 1 or self._preempted
                        or (cfg.ckpt_every
                            and (step + 1) % cfg.ckpt_every == 0)
                        or (cfg.profile and is_log(step)))

        def read_back(step, metrics, t0, queued):
            """``step``'s scalars in one transfer (``wait``), then its host
            work (``host``).  ``queued``: the next step is already
            dispatched.  Without it ``params``/``opt_state`` are still
            this step's, so its profile sample, checkpoint and preemption
            run here; returns True where a preemption ends the run."""
            nonlocal prev_ref, last_read
            log = is_log(step)
            with tracker.span('wait', step):
                host = jax.device_get({k: v for k, v in metrics.items()
                                       if log or k != 'grad_norm'})
            t = time.perf_counter()
            dt, last_read = t - max(t0, last_read), t
            counts['overlapped' if queued else 'drained'] += 1
            with tracker.span('host', step):
                loss = float(host['loss'])
                if step == start_step:
                    fresh = recorder.comm_sites()
                    if fresh:
                        self._run_sites = fresh
                    self._log_comm(recorder, getattr(self, '_run_sites', {}))
                self._watchdog.observe(step, dt)
                history.append(loss)
                sched_fields = obs_events.step_fields(host)
                if 'refreshes' in sched_fields:
                    cur_ref = sched_fields['refreshes']
                    if cur_ref > prev_ref:
                        recorder.emit('refresh', step=step,
                                      refreshes=cur_ref,
                                      step_time_s=round(dt, 6))
                    prev_ref = cur_ref
                if log:
                    self._log_step(recorder, step, start_step, ref_base,
                                   host, loss, dt, sched_fields)
                if queued:
                    return False
                if cfg.profile and log:
                    self._emit_profile(recorder, step, self.step_fn,
                                       (params, opt_state, batch),
                                       one_shot_hlo=(step == start_step))
                if cfg.ckpt_every and (step + 1) % cfg.ckpt_every == 0:
                    self._ckptr.save(step + 1,
                                     {'params': params,
                                      'opt_state': opt_state},
                                     {'next_step': step + 1})
                if self._preempted:
                    print('[trainer] preemption: synchronous checkpoint '
                          f'at step {step + 1}', flush=True)
                    self._ckptr.wait()
                    ckpt.save(self.ckpt_dir, step + 1,
                              {'params': params, 'opt_state': opt_state},
                              {'next_step': step + 1, 'preempted': True})
                    return True
            return False

        pending = None      # (step, metrics, t0): dispatched, not read back
        try:
            for step in range(start_step, cfg.total_steps):
                with jax.profiler.StepTraceAnnotation('train', step_num=step):
                    with tracker.span('data', step):
                        batch = data.batch_at(step)
                    t0 = time.perf_counter()
                    with tracker.span('dispatch', step), \
                            attn.collect() as traced:
                        params, opt_state, metrics = self.step_fn(
                            params, opt_state, batch)
                    self._log_attention(recorder, traced)
                    if pending is not None:
                        read_back(*pending, queued=True)
                    pending = (step, metrics, t0)
                if must_drain(step):
                    pending = None
                    with jax.profiler.TraceAnnotation('drain', step=step):
                        if read_back(step, metrics, t0, queued=False):
                            break
            recorder.emit('loop', steps=sum(counts.values()), **counts)
        finally:
            self._ckptr.wait()
            self._watchdog.recorder = None
            recorder.close()
        return params, opt_state, history

    def _log_step(self, recorder, step, start_step, ref_base, metrics, loss,
                  dt, sched_fields):
        """``fit``'s ``step`` record and log line."""
        rec = {'step': step, 'loss': loss,
               'grad_norm': float(metrics['grad_norm']),
               'step_time_s': round(dt, 4), **sched_fields}
        sched_line = ''
        if 'refreshes' in rec:
            sched_line = (f" refreshes {rec['refreshes']}"
                          f" staleness {rec['staleness']:.3g}")
        if 'pipeline_lag' in rec:
            sched_line += f" lag {rec['pipeline_lag']}"
        # cumulative exchanged bytes, from THIS trainer's comm sites:
        # per-step sites (grads/stats) fire every step, refresh sites once
        # per realized refresh
        sites = getattr(self, '_run_sites', {})
        if sites:
            step_b = sum(v['bytes_per_call'] for s, v in sites.items()
                         if not s.startswith('refresh/'))
            refresh_b = sum(v['bytes_per_call'] for s, v in sites.items()
                            if s.startswith('refresh/'))
            rec['exchanged_mb_cum'] = round(
                (step_b * (step + 1 - start_step)
                 + refresh_b * (rec.get('refreshes', ref_base) - ref_base))
                / 2 ** 20, 3)
        recorder.emit('step', **rec)
        print(f'[trainer] step {step:6d} loss {loss:.4f} '
              f'({dt*1e3:.0f} ms){sched_line}', flush=True)

    # -- elastic outer loop ---------------------------------------------------

    def fit_elastic(self, params, data: Any, world: Optional[int] = None,
                    world_fn: Optional[Callable[[int], Optional[int]]] = None,
                    start_step: int = 0, resume: bool = True):
        """Elastic training: tolerate worker-count changes *between* steps.

        The run executes as a sequence of constant-W data-parallel phases
        over a ``('data',)`` mesh of the first W local devices
        (``launch.mesh.make_data_mesh``), stepping through the explicit-DP
        ``make_dp_step``.  W starts at ``world`` (default: every local
        device) and may change two ways:

        * **restore** — a checkpoint written at a different W (its
          ``elastic`` metadata block says which, docs/CHECKPOINT_FORMAT.md)
          is restored leaf-for-leaf, then resharded;
        * **live** — ``world_fn(step)`` (None = keep current) requests a
          new W between steps, modeling workers being killed or re-added.

        Either way the loop runs restore → reshard
        (``schedule.reshard.reshard_state``: ownership re-derives from the
        new (plan, W) at trace time, in-flight pipeline buffers drain to
        the documented cold start) → rebuild mesh → re-jit → continue, and
        emits a typed ``reshard`` event plus a fresh ``refresh_ownership``
        map through ``repro.obs``.  Checkpoints written by this loop carry
        the elastic metadata block, and the preemption contract (SIGTERM →
        synchronous checkpoint → clean exit) is inherited from :meth:`fit`.

        At W=1 the trajectory is bit-identical to :meth:`fit` (size-1
        collectives are exact); across W the global batch mean is the same
        up to float reduction order.  Unlike :meth:`fit` it reads each
        step back before dispatching the next (``_run_step``): a live
        resize between steps needs the step drained.  Each ``train``
        annotation holds its own step's four spans (a live resize runs in
        the ``host`` span of the step before it); ``profile`` mode's
        records are :meth:`fit`'s.

        Returns ``(params, opt_state, history)`` with ``history`` a list of
        ``(step, loss)`` pairs (steps matter: a resumed run starts mid-way).
        """
        from repro.launch.mesh import make_data_mesh

        cfg = self.cfg
        self._install_signal_handlers()
        world = int(world) if world else jax.device_count()

        # the bucket plan is the reshard key: ownership maps and the
        # checkpoint fingerprint both derive from it (None = first-order)
        plan = stats_plan_of(self.model, self.capture, params,
                             data.batch_at(start_step), taps_fn=self.taps_fn)

        def _init_state(step):
            return init_opt_state(self.model, self.opt, self.capture, params,
                                  data.batch_at(step), taps_fn=self.taps_fn,
                                  sched=self.sched, comm=self.comm,
                                  factor=self.factor)

        opt_state = None
        world_from = world
        source = 'init'
        if resume and cfg.ckpt_every:
            latest = ckpt.latest_step(self.ckpt_dir)
            if latest is not None:
                template = {'params': params, 'opt_state': _init_state(0)}
                state, meta = ckpt.restore(self.ckpt_dir, latest, template)
                params, opt_state = state['params'], state['opt_state']
                start_step = meta.get('next_step', latest)
                ck_world = reshard_mod.check_metadata(
                    meta.get(reshard_mod.ELASTIC_KEY),
                    plan=plan, pipeline=self.sched.pipeline)
                world_from = ck_world if ck_world else world
                source = 'checkpoint'
                print(f'[trainer] resumed from step {latest} '
                      f'(checkpoint W={world_from})', flush=True)
        if opt_state is None:
            opt_state = _init_state(start_step)

        if cfg.donate:
            # same caller-owned-buffer guard as fit: the jitted step
            # donates its inputs
            params = jax.tree_util.tree_map(
                lambda x: x + 0 if hasattr(x, 'dtype') else x, params)
            opt_state = jax.tree_util.tree_map(
                lambda x: x + 0 if hasattr(x, 'dtype') else x, opt_state)

        base_sched = schedrt.schedule_metrics(opt_state)
        ref_base = int(base_sched['refreshes']) if base_sched else 0

        recorder = obs_events.Recorder(self.metrics_path)
        self._watchdog.recorder = recorder
        step_fns: dict[int, Callable] = {}  # W -> compiled step (re-expand
                                            # to a previous W reuses it)
        step_fn = None

        check_batch_next = True  # re-validated at start and on every resize

        def _resize(w_from, w_to, at_step, src):
            nonlocal params, opt_state, step_fn, world, check_batch_next
            check_batch_next = True
            opt_state, body = reshard_mod.reshard_state(
                opt_state, world_from=w_from, world_to=w_to, plan=plan,
                step=at_step, source=src)
            mesh = make_data_mesh(w_to)
            replicated = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec())
            # explicit placement: a live shrink/grow leaves the old arrays
            # committed to the previous mesh's devices
            params = jax.device_put(params, replicated)
            opt_state = jax.device_put(opt_state, replicated)
            if w_to not in step_fns:
                dp = make_dp_step(self.model, self.opt, self.capture, mesh,
                                  taps_fn=self.taps_fn, sched=self.sched,
                                  comm=self.comm, factor=self.factor)
                step_fns[w_to] = jax.jit(
                    dp, donate_argnums=(0, 1) if cfg.donate else ())
            step_fn = step_fns[w_to]
            world = w_to
            if w_from != w_to:
                recorder.emit('reshard', **body)
                print(f"[trainer] reshard W={w_from} -> W={w_to} at step "
                      f"{at_step} (pipeline buffers: {body['pipeline']}, "
                      f"owners moved: {body.get('slices_moved', 0)}/"
                      f"{body.get('slices_total', 0)})", flush=True)
            own = schedrt.ownership_event(plan, world=w_to)
            if own is not None:
                recorder.emit('refresh_ownership', **own)

        _resize(world_from, world, start_step, source)

        def _meta(next_step, **extra):
            return {'next_step': next_step,
                    reshard_mod.ELASTIC_KEY: reshard_mod.elastic_metadata(
                        world, plan=plan, pipeline=self.sched.pipeline),
                    **extra}

        def _follow_world(step):
            if world_fn is not None:
                want = world_fn(step)
                if want and int(want) != world:
                    _resize(world, int(want), step, 'live')

        def _check_batch(batch):
            nonlocal check_batch_next
            if check_batch_next:
                reshard_mod.check_batch_divisible(batch, world)
                check_batch_next = False

        tracker = obs_spans.SpanTracker(recorder if cfg.profile else None)
        history: list[tuple[int, float]] = []
        prev_ref = ref_base
        try:
            if start_step < cfg.total_steps:
                _follow_world(start_step)
            for step in range(start_step, cfg.total_steps):
                with jax.profiler.StepTraceAnnotation('train', step_num=step):
                    params, opt_state, metrics, batch, loss, dt = \
                        self._run_step(tracker, step_fn, data, step, params,
                                       opt_state, check=_check_batch)
                    with tracker.span('host', step):
                        if step == start_step:
                            fresh = recorder.comm_sites()
                            if fresh:
                                self._run_sites = fresh
                            self._log_comm(recorder,
                                           getattr(self, '_run_sites', {}))
                        self._watchdog.observe(step, dt)
                        history.append((step, loss))
                        sched_fields = obs_events.step_fields(metrics)
                        if 'refreshes' in sched_fields:
                            cur_ref = sched_fields['refreshes']
                            if cur_ref > prev_ref:
                                recorder.emit('refresh', step=step,
                                              refreshes=cur_ref,
                                              step_time_s=round(dt, 6))
                            prev_ref = cur_ref
                        if step % cfg.log_every == 0 \
                                or step == cfg.total_steps - 1:
                            recorder.emit(
                                'step', step=step, loss=loss,
                                grad_norm=float(metrics['grad_norm']),
                                step_time_s=round(dt, 4), **sched_fields)
                            if cfg.profile:
                                self._emit_profile(
                                    recorder, step, step_fn,
                                    (params, opt_state, batch),
                                    one_shot_hlo=(step == start_step))
                            print(f'[trainer] step {step:6d} loss '
                                  f'{loss:.4f} ({dt*1e3:.0f} ms) W={world}',
                                  flush=True)
                        if cfg.ckpt_every and (step + 1) % cfg.ckpt_every == 0:
                            self._ckptr.save(step + 1,
                                             {'params': params,
                                              'opt_state': opt_state},
                                             _meta(step + 1))
                        if self._preempted:
                            print('[trainer] preemption: synchronous '
                                  f'checkpoint at step {step + 1}',
                                  flush=True)
                            self._ckptr.wait()
                            ckpt.save(self.ckpt_dir, step + 1,
                                      {'params': params,
                                       'opt_state': opt_state},
                                      _meta(step + 1, preempted=True))
                            break
                        if step + 1 < cfg.total_steps:
                            _follow_world(step + 1)
        finally:
            self._ckptr.wait()
            self._watchdog.recorder = None
            recorder.close()
        return params, opt_state, history
