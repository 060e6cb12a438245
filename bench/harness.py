"""One run of one benchmark cell: set-up, timed window, readings, check.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file found by name: ``BENCHMARK.json`` names the cell's
configuration (``bench/configs/<config>.json``) and traffic
(``bench/traffic/<traffic>.json``); the cell's correctness limits are in
``bench/limits/<cell>.json``; each per-layer metric is read by
``bench/metrics/<metric>.py``.  The plain reference is the configuration's
model (``bench/reference/<reference>.py``, named in its file) driven by the
traffic's optimizer (``bench/optimizers/<name>.py``, named by the traffic's
``optimizer.name``, whose other keys go untouched to both the program's
``make_optimizer`` and the reference).

The timed path is the program's production training loop,
``repro.train.Trainer.fit``, built as its launcher builds it, around the
program's ``Prefetcher`` over this benchmark's token stream.  Set-up builds
that one trainer, drives it from the seed through the checked first steps
(read for the comparison with the reference), warms it up, and hands the
same trainer and state to the window.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import statistics
import sys
import time
from pathlib import Path
from typing import Optional

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / 'bench'
TRACE_SECONDS = 4.0      # length of the traced window (--trace 1)
WARM_STEPS = 4           # steps after the checked ones, timed to size the window

# keys of a configuration file that must equal the program's config
ARCH_KEYS = ('n_layers', 'd_model', 'n_heads', 'n_kv_heads', 'head_dim',
             'd_ff', 'vocab', 'qkv_bias', 'tie_embeddings', 'rope_theta',
             'param_dtype', 'compute_dtype', 'remat', 'attn_impl')


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


_MODULES: dict = {}


def load_module(path: Path, name: str):
    if path not in _MODULES:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    traffic: dict
    limits: dict
    end_to_end: list        # BENCHMARK.json entries this cell reports
    per_layer: list
    root: Path = ROOT       # the checkout whose bench/ holds its files

    @property
    def tokens_per_step(self) -> int:
        return self.traffic['batch'] * self.traffic['seq_len']

    @property
    def optimizer_kwargs(self) -> dict:
        return {k: v for k, v in self.traffic['optimizer'].items()
                if k != 'name'}

    def model_reference(self):
        name = self.cfg['reference']
        return load_module(self.root / 'bench' / 'reference' / f'{name}.py',
                           f'bench_reference_{name}')

    def optimizer(self):
        name = self.traffic['optimizer']['name']
        return load_module(self.root / 'bench' / 'optimizers' / f'{name}.py',
                           f'bench_optimizer_{name}')

    def metric_reader(self, name: str):
        return load_module(self.root / 'bench' / 'metrics' / f'{name}.py',
                           f"bench_metric_{name.replace('.', '_')}")


def _applies(entry: dict, cell: str) -> bool:
    return 'workloads' not in entry or cell in entry['workloads']


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / 'BENCHMARK.json').read_text())
    wl = {w['name']: w for w in bench['workloads']}
    if name not in wl:
        raise SystemExit(f'unknown workload {name!r}; have {sorted(wl)}')
    w = wl[name]
    cfg_entry = {c['name']: c for c in bench['configs']}[w['config']]
    bench_dir = root / 'bench'
    return Cell(
        name=name, chips=w['chips'],
        cfg=json.loads((root / cfg_entry['file']).read_text()),
        traffic=json.loads((bench_dir / 'traffic'
                            / f"{w['traffic']}.json").read_text()),
        limits=json.loads((bench_dir / 'limits'
                           / f'{name}.json').read_text())['limits'],
        end_to_end=[m for m in bench['end_to_end'] if _applies(m, name)],
        per_layer=[m for m in bench['per_layer'] if _applies(m, name)],
        root=root)


# ---------------------------------------------------------------------------
# host-side instruments


class CompileLog:
    """Times at which JAX fetched a compiled program (a compile or a hit
    in the persistent cache): both go through the backend-compile event.
    One listener per process; runs read the times they care about."""
    EVENT = '/jax/core/compile/backend_compile_duration'
    _times: Optional[list] = None

    @classmethod
    def times(cls) -> list:
        if cls._times is None:
            import jax
            cls._times = []

            def listen(event, duration, **kw):
                if event == cls.EVENT:
                    cls._times.append(time.perf_counter())

            jax.monitoring.register_event_duration_secs_listener(listen)
        return cls._times


class GcLog:
    """Python's cyclic collections (generation, start, end), from
    ``gc.callbacks``: a full collection over a heap of traced programs can
    stall the step loop, and the run reports those inside the window."""

    def __init__(self):
        self.pauses: list = []
        self._t0 = None
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == 'start':
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append((info['generation'], self._t0,
                                time.perf_counter()))

    def close(self):
        gc.callbacks.remove(self._cb)


class TimedSource:
    """The data source ``fit`` reads, wrapped: every ``batch_at`` request is
    recorded (step, entry time, exit time) and spans a ``bench.data``
    trace annotation."""

    def __init__(self, inner):
        self.inner = inner
        self.calls: list = []

    def batch_at(self, step: int):
        import jax
        with jax.profiler.TraceAnnotation('bench.data', step=step):
            t0 = time.perf_counter()
            batch = self.inner.batch_at(step)
            t1 = time.perf_counter()
        self.calls.append((step, t0, t1))
        return batch


def loop_calls(calls: list, first_step: int) -> list:
    """The requests of ``fit``'s step loop: from the last request for its
    first step (``fit`` also asks for that batch once before the loop)."""
    idx = max(i for i, c in enumerate(calls) if c[0] == first_step)
    return calls[idx:]


def step_times(loop: list, t_end: float) -> list:
    """Each step: from its batch request to the next step's (the last: to
    the end of the window)."""
    starts = [c[1] for c in loop] + [t_end]
    return [b - a for a, b in zip(starts, starts[1:])]


# ---------------------------------------------------------------------------
# the program under test


class Program:
    """The program's trainer for one cell, built as its launcher builds it."""

    def __init__(self, cell: Cell, out_dir: Path, fault=None):
        if not (ROOT / 'src' / 'repro').is_dir():
            raise SystemExit(f'no program sources at {ROOT / "src"}')
        if str(ROOT / 'src') not in sys.path:
            sys.path.insert(0, str(ROOT / 'src'))
        import jax
        from repro.configs import get_config
        from repro.core import make_optimizer
        from repro.models import build_model
        from repro.train import Trainer, TrainerConfig

        cfg, tr = cell.cfg, cell.traffic
        arch = get_config(cfg['program_arch']).replace(
            **{k: cfg[k] for k in cfg['reduced']})
        for k in ARCH_KEYS:
            if getattr(arch, k) != cfg[k]:
                raise SystemExit(f'{cfg["name"]}: program config {k} = '
                                 f'{getattr(arch, k)!r}, file says {cfg[k]!r}')
        self.model = build_model(arch)
        o = dict(tr['optimizer'])
        self.opt, self.capture = make_optimizer(o.pop('name'), **o)
        kernel = None
        if tr.get('kernel_impl'):
            from repro.kernels.dispatch import KernelConfig
            kernel = KernelConfig(impl=tr['kernel_impl'])
        self.trainer = Trainer(
            self.model, self.opt, self.capture,
            TrainerConfig(total_steps=0, log_every=10 ** 9, ckpt_every=0,
                          out_dir=str(out_dir)), kernel=kernel)
        self.jitted_step = self.trainer.step_fn
        if fault is not None:
            self.trainer.step_fn = fault(self.trainer.step_fn)
        self.param_shapes = {
            p: (tuple(s.shape), str(s.dtype)) for p, s in _flatten(
                jax.eval_shape(self._abstract_params)).items()}

    def _abstract_params(self):
        import jax
        from repro.models import module as M
        return M.init_params(self.model.param_specs(), jax.random.PRNGKey(0))

    def fit(self, live: dict, data, start: int, stop: int) -> list:
        """Steps ``start`` to ``stop`` of ``fit``.  ``live`` holds the
        parameters (and, after the first call, the optimizer state); they
        are handed over, so that no frame here keeps them alive beside
        ``fit``'s pre-donation copy -- as the program's launcher does."""
        self.trainer.cfg.total_steps = stop
        params, opt_state, hist = self.trainer.fit(
            live.pop('params'), data, start_step=start,
            opt_state=live.pop('opt', None), resume=False)
        live['params'], live['opt'] = params, opt_state
        return hist


def _flatten(tree, prefix: str = '') -> dict:
    if isinstance(tree, dict):
        out = {}
        for k, v in tree.items():
            out.update(_flatten(v, f'{prefix}/{k}' if prefix else str(k)))
        return out
    return {prefix: tree}


def param_shaped(opt_state, params) -> list:
    """Every subtree of the optimizer state shaped like the parameters."""
    import jax
    target = jax.tree_util.tree_structure(params)
    found = []

    def walk(x):
        if isinstance(x, dict) and jax.tree_util.tree_structure(x) == target:
            found.append(x)
        elif isinstance(x, (tuple, list)):
            for c in x:
                walk(c)
        elif isinstance(x, dict):
            for c in x.values():
                walk(c)

    walk(opt_state)
    return found


def leaf_norms(tree) -> dict:
    import jax
    import jax.numpy as jnp
    norms = jax.jit(lambda t: {p: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for p, x in _flatten(t).items()})(tree)
    return {p: float(v) for p, v in norms.items()}


def change_norms(params, params0) -> dict:
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda a, b: {p: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32) - y.astype(jnp.float32))))
        for (p, x), y in zip(_flatten(a).items(), _flatten(b).values())})
    return {p: float(v) for p, v in f(params, params0).items()}


# ---------------------------------------------------------------------------
# one run


@dataclasses.dataclass
class View:
    """What a per-layer metric reader sees of a traced run.  The traced
    window opens at the request for the window's second step: ``fit`` asks
    for its first batch twice, and the prefetcher's refill then is a wait
    paid once per ``fit`` call, not per step."""
    cell: Cell
    peaks: dict
    chips: int
    steps: int                  # steps in the traced window
    window_s: float             # traced window, on the trace's clock
    busy_s: float               # device busy, averaged over the chips
    ops: list                   # device ops of each chip used ([Span])
    modules: list               # device program runs of each chip ([Span])
    w0: float
    w1: float
    data_wait_s: list           # per step of the traced window
    hbm_peak_bytes: Optional[int]
    flops_per_token: float


def read_peaks(kind: str) -> dict:
    table = json.loads((BENCH / 'peaks.json').read_text())
    if kind not in table['devices']:
        raise SystemExit(f'device kind {kind!r} is not in bench/peaks.json')
    return table['devices'][kind]


def reference(cell: Cell, precision: str = 'f32'):
    """The cell's plain reference: its configuration's model driven by its
    traffic's optimizer, at ``precision`` (``'fp8'``: the control)."""
    opt = cell.optimizer().Reference(**cell.optimizer_kwargs)
    return cell.model_reference().Reference(cell.cfg, opt, precision)


def reference_run(cell: Cell, ref, seed: int, stream) -> dict:
    """``ref`` through the checked steps, from the seed's weights and
    batches: the readings that the comparison takes."""
    model = cell.model_reference()
    return ref.run(model.init_weights(cell.cfg, seed),
                   model.batches_for(stream, cell.traffic['checked_steps']))


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_process: float, require_tpu: bool = True, fault=None,
             program_as_reference: Optional[str] = None,
             work_dir: Optional[Path] = None,
             log=lambda s: print(s, file=sys.stderr, flush=True)) -> dict:
    """Set up, measure, check.  Returns the result line as a dict.

    ``fault`` wraps the trainer's jitted step (tests plant faults this way);
    ``program_as_reference`` puts the reference in the program's place at
    that precision (the control); ``work_dir`` holds the trainer's run
    directory and the trace (default ``<checkout>/.bench``)."""
    import jax
    from bench.traffic import TokenStream
    from bench import compare

    devs = jax.devices()
    if require_tpu:
        if devs[0].platform != 'tpu':
            raise NoChip(f'JAX finds no TPU (platform {devs[0].platform!r})')
        if len(devs) < cell.chips:
            raise NoChip(f'the cell needs {cell.chips} chips, JAX finds '
                         f'{len(devs)}')
    kind = devs[0].device_kind
    # off the chip (the harness's own tests) no device number is meaningful
    peaks = read_peaks(kind) if require_tpu else \
        {'bf16_flops': math.nan, 'hbm_bytes_per_s': math.nan}
    cfg = cell.cfg
    compiles = CompileLog.times()
    stream = TokenStream.from_traffic(cell.traffic, cfg['vocab'], seed)

    if program_as_reference:
        prog = reference_run(cell, reference(cell, program_as_reference),
                             seed, stream)
        result = {'correct': None, 'attempted': cell.traffic['checked_steps'],
                  'failed': 0, 'metrics': {}}
        window = None
    else:
        work_dir = Path(work_dir or ROOT / '.bench')
        program = Program(cell, work_dir / 'runs' / cell.name, fault=fault)
        layout = {p: (tuple(s), cfg['param_dtype']) for p, (s, _) in
                  cell.model_reference().param_layout(cfg).items()}
        if program.param_shapes != layout:
            raise SystemExit(f'{cfg["name"]}: the program takes parameters '
                             f'{program.param_shapes}, the reference makes '
                             f'{layout}')
        from repro.data import Prefetcher
        data = TimedSource(Prefetcher(stream))
        try:
            prog, result, window = _drive(
                cell, program, data, seed, seconds, trace, t_process,
                compiles, peaks, log, work_dir)
        finally:
            data.inner.close()
        del program

    t_ref = time.perf_counter()
    ref = reference_run(cell, reference(cell), seed, stream)
    log(f'reference: {time.perf_counter() - t_ref:.1f} s')
    values = compare.readings(prog, ref)
    log(f'worst leaves: {compare.worst_leaves(prog, ref)}')
    checks = compare.checks(values, cell.limits)
    result['correct'] = compare.passed(checks) and result['failed'] == 0
    result['device'] = {'platform': devs[0].platform, 'kind': kind,
                        'count': len(devs), **result.get('device', {})}
    if window is not None:
        result['device'].update(window)
    for name, c in checks.items():
        log(f'check {name} {c["value"]!r} limit {c["limit"]!r}')
    result['checks'] = checks
    return result


def checked_steps(cell: Cell, program, data, seed: int):
    """Drive the program from the seed through the checked first steps, by
    the timed path's own call and feed.  Returns its readings (each step's
    loss, per-leaf norms of the optimizer's first update, as its state
    keeps it after step 1, and of the parameters' change over the checked
    steps) and the live state."""
    model = cell.model_reference()
    live = {'params': model.init_weights(cell.cfg, seed)}
    losses = program.fit(live, data, 0, 1)
    update1 = leaf_norms(cell.optimizer().program_first_update(
        live['opt'], live['params']))
    losses += program.fit(live, data, 1, cell.traffic['checked_steps'])
    params0 = model.init_weights(cell.cfg, seed)
    change = change_norms(live['params'], params0)
    del params0
    return {'losses': losses, 'update1': update1, 'change': change}, live


def _drive(cell, program, data, seed, seconds, trace, t_process, compiles,
           peaks, log, work_dir):
    import jax
    import numpy as np
    prog, live = checked_steps(cell, program, data, seed)
    # warm-up, timed to size the window
    checked = cell.traffic['checked_steps']
    stop = checked + WARM_STEPS
    program.fit(live, data, checked, stop)
    warm = step_times(loop_calls(data.calls, checked), time.perf_counter())
    t_step = statistics.median(warm[1:])
    if trace:
        # one step more: the traced window opens at the second (see View)
        n = max(2, round(min(seconds, TRACE_SECONDS) / t_step)) + 1
    else:
        n = max(2, round(seconds / t_step))
    trace_dir = work_dir / 'trace' / cell.name
    if trace:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    gc_log = GcLog()
    try:
        with jax.profiler.TraceAnnotation('bench.window'):
            hist = program.fit(live, data, stop, stop + n)
            jax.block_until_ready(live['params'])
            t_end = time.perf_counter()
    finally:
        if trace:
            jax.profiler.stop_trace()
    loop = loop_calls(data.calls, stop)
    t_start = loop[0][1]
    in_window = sum(1 for t in compiles if t_start <= t <= t_end)
    log(f'compilations in the window: {in_window}')
    gcs = [(g, round(1e3 * (b - a), 1)) for g, a, b in gc_log.pauses
           if t_start <= a <= t_end and (g == 2 or b - a > 0.01)]
    gc_log.close()
    log(f'Python collections in the window (generation, ms), full or over '
        f'10 ms: {gcs}')
    if in_window:
        raise SystemExit(f'{in_window} compilation(s) inside the window')
    times = step_times(loop, t_end)
    waits = [c[2] - c[1] for c in loop]
    failed = sum(1 for x in hist if not math.isfinite(x))
    # the compiled step's own count: the runtime's peak misses temporaries
    t_hbm = time.perf_counter()
    hbm = _compiled_peak(program, live['params'], live['opt'],
                         data.inner.batch_at(0))
    log(f'compiled step peak: {hbm} bytes '
        f'({time.perf_counter() - t_hbm:.1f} s)')
    live.clear()
    mem = max([hbm or 0] + [(d.memory_stats() or {}).get(
        'peak_bytes_in_use', 0) for d in jax.devices()[:cell.chips]])
    result: dict = {'attempted': n, 'failed': failed,
                    'device': {'memory_peak_bytes': int(mem)}}
    window = None
    fpt = cell.model_reference().flops_per_token(cell.cfg,
                                                 cell.traffic['seq_len'])
    if trace:
        result['metrics'], result['breakdown'], window = _per_layer(
            cell, trace_dir, peaks, waits[1:], hbm, fpt)
    else:
        window_s = t_end - t_start
        tok_s = n * cell.tokens_per_step / window_s
        values = {
            'tokens_per_s': tok_s,
            'mfu': 100.0 * tok_s * fpt / (cell.chips * peaks['bf16_flops']),
            'step_ms_p90': 1e3 * float(np.percentile(times, 90)),
            'setup_s': t_start - t_process}
        result['metrics'] = {m['name']: {'value': values[m['name']],
                                         'unit': m['unit']}
                             for m in cell.end_to_end}
        med = statistics.median(times)
        log(f'window: {n} steps in {window_s:.3f} s, step median '
            f'{1e3 * med:.2f} ms')
        slow = [(loop[i][0], round(1e3 * t, 1), round(1e3 * waits[i], 1))
                for i, t in enumerate(times) if t > 2 * med]
        log(f'steps over twice the median (step, ms, of which data wait '
            f'ms): {slow}')
    return prog, result, window


def _compiled_peak(program, params, state, batch) -> Optional[int]:
    import jax
    spec = lambda t: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        t)
    compiled = program.jitted_step.lower(
        spec(params), spec(state), spec(batch)).compile()
    mem = compiled.memory_analysis()
    return None if mem is None else int(mem.peak_memory_in_bytes)


def _per_layer(cell, trace_dir, peaks, waits, hbm, fpt):
    import shutil
    from bench import trace as tr_mod
    files = sorted(trace_dir.glob('**/*.xplane.pb'))
    if not files:
        raise SystemExit('the profiler wrote no trace')
    trace = tr_mod.load(str(files[-1]))
    shutil.rmtree(trace_dir, ignore_errors=True)
    w = tr_mod.window(trace)
    if w is None:
        raise SystemExit('no measured window in the trace')
    w0, w1 = w
    planes = sorted(trace.ops)[:cell.chips]
    ops = [trace.ops[p] for p in planes]
    busy = statistics.fmean(tr_mod.busy_ns(o, w0, w1) for o in ops) / 1e9
    view = View(cell=cell, peaks=peaks, chips=cell.chips, steps=len(waits),
                window_s=(w1 - w0) / 1e9, busy_s=busy, ops=ops,
                modules=[trace.modules.get(p, []) for p in planes],
                w0=w0, w1=w1, data_wait_s=waits, hbm_peak_bytes=hbm,
                flops_per_token=fpt)
    metrics = {}
    for m in cell.per_layer:
        value = cell.metric_reader(m['name']).read(view)
        if value is not None:
            metrics[m['name']] = {'value': value, 'unit': m['unit']}
    selfs = tr_mod.self_times(ops[0], w0, w1)
    top = sorted(selfs.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(tr_mod.gaps(ops[0], w0, w1), key=lambda g: g[0] - g[1])
    breakdown = {
        'device_ops': [[k, v / 1e9] for k, v in top],
        'idle_gaps': [[tr_mod.host_label(trace, a, b), (b - a) / 1e9]
                      for a, b in gaps[:10]]}
    return metrics, breakdown, {'busy_s': busy, 'window_s': (w1 - w0) / 1e9}
