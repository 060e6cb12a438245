"""Host spans of the training loop, the straggler watchdog, and
profile-mode samplers.

``SpanTracker.span(name, step)`` enters a
``jax.profiler.TraceAnnotation('train.<name>', step=step)``: under any
``jax.profiler`` capture the span lands on the host plane of the same
``.xplane.pb`` as the device's ops, on one clock, and costs a flag check
when no profiler runs.  The span waits on nothing: JAX dispatch is
asynchronous, so its host time is the time the loop spent there (for
``wait``, the device's remaining work plus the read-back), and the device
side of the step is read from the device plane, where ``jax.named_scope``
names every op (``train/step.py``).  Built with a recorder (the trainer's
``profile`` mode), the tracker also keeps each closed span in ``records``
and emits it as a ``span`` record; without one it keeps nothing.

Profile mode additionally samples per-step live-buffer bytes
(``jax.live_arrays``), device-memory stats where the backend has them, and
a one-shot HLO cost + blocking-collective summary of the compiled step
(``launch/hlo_analysis``).
"""
from __future__ import annotations

import contextlib
import statistics
import time
from typing import Iterator, Optional

import jax

from repro.obs import events


class SpanTracker:
    """Named host spans on the profiler's trace; with a recorder, also one
    ``span`` record per closed span, with nesting metadata
    (``depth``/``parent``) and a global emission order (``seq``)."""

    def __init__(self, recorder: Optional[events.Recorder] = None,
                 clock=time.perf_counter):
        self.recorder = recorder
        self.records: list[dict] = []
        self._clock = clock
        self._stack: list[str] = []
        self._seq = 0

    @contextlib.contextmanager
    def span(self, name: str, step: Optional[int] = None) -> Iterator[None]:
        kw = {} if step is None else {'step': int(step)}
        with jax.profiler.TraceAnnotation(f'train.{name}', **kw):
            if self.recorder is None:
                yield
                return
            parent = self._stack[-1] if self._stack else None
            depth = len(self._stack)
            self._stack.append(name)
            t0 = self._clock()
            try:
                yield
            finally:
                ms = (self._clock() - t0) * 1e3
                self._stack.pop()
                rec = {'name': name, 'ms': round(ms, 4), 'seq': self._seq,
                       'depth': depth, 'parent': parent, **kw}
                self._seq += 1
                self.records.append(rec)
                self.recorder.emit('span', **rec)


class StragglerWatchdog:
    """Median-of-window straggler detection (factored out of the trainer so
    injected timings can drive it in tests).

    ``observe(step, dt)`` returns True — and emits a ``straggler`` record —
    when ``dt`` exceeds ``factor ×`` the median of the last ``window``
    step times (current step included, matching the original trainer
    logic); needs ``min_history`` samples before it can trigger.  On a
    real pod this feeds the controller that evicts/replaces the slow host.
    """

    def __init__(self, factor: float = 3.0,
                 recorder: Optional[events.Recorder] = None,
                 window: int = 64, min_history: int = 8):
        self.factor = factor
        self.recorder = recorder
        self.window = window
        self.min_history = min_history
        self.times: list[float] = []

    def observe(self, step: int, dt: float) -> bool:
        self.times.append(dt)
        if len(self.times) < self.min_history:
            return False
        med = statistics.median(self.times[-self.window:])
        if dt <= self.factor * med:
            return False
        if self.recorder is not None:
            self.recorder.emit('straggler', step=int(step),
                               step_time_s=round(dt, 6),
                               median_s=round(med, 6), factor=self.factor)
        print(f'[obs] STRAGGLER step {step}: {dt*1e3:.0f} ms vs median '
              f'{med*1e3:.0f} ms — flagged for controller', flush=True)
        return True


# ---------------------------------------------------------------------------
# Profile-mode samplers


def live_buffer_mb() -> float:
    """Total bytes of live device arrays in this process, in MiB."""
    return round(sum(a.nbytes for a in jax.live_arrays()) / 2 ** 20, 3)


def device_bytes_in_use() -> Optional[int]:
    """Allocator bytes-in-use of device 0, where the backend reports it
    (TPU/GPU; the CPU backend returns None)."""
    stats = jax.local_devices()[0].memory_stats()
    if not stats or 'bytes_in_use' not in stats:
        return None
    return int(stats['bytes_in_use'])


def hlo_costs(compiled_text: str) -> dict:
    """One compiled fn's HLO cost + blocking-collective summary — the
    ``fns`` entries of a ``profile`` record (trip-count-aware, reusing
    ``launch/hlo_analysis``)."""
    from repro.launch import hlo_analysis
    costs = hlo_analysis.analyze(compiled_text)
    overlap = hlo_analysis.collective_overlap(compiled_text)
    dep_frac = (overlap.dot_flops_dependent / overlap.dot_flops_total
                if overlap.dot_flops_total else 0.0)
    return {
        'flops': costs.flops,
        'traffic_bytes': costs.traffic_bytes,
        'collective_bytes': costs.collective_bytes,
        'collective_count': overlap.collective_count,
        'blocking_collectives': overlap.blocking_collectives,
        'dependent_dot_flop_frac': round(dep_frac, 4),
    }


def compiled_fn_costs(jitted_fn, *args) -> dict:
    """``hlo_costs`` of a jitted fn lowered at ``args``' shapes."""
    text = jitted_fn.lower(*args).compile().as_text()
    return hlo_costs(text)
