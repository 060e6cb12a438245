"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

Read with ``jax.profiler.ProfileData``: each ``/device:TPU:<n>`` plane has an
``XLA Ops`` line whose events are the operations the device ran, named by
their HLO instruction text (``%fusion.12 = bf16[...] fusion(...)``; a Pallas
kernel is ``%<kernel>.<n> = ... custom-call(...)``).  Events nest: a
``while`` loop's event spans the ops of its body.  Its ``XLA Modules`` line
holds one event per run of a compiled program (``jit_train_step(<hash>)``).  The ``/host:CPU`` plane
holds the harness's own ``TraceAnnotation`` spans on the thread that made
them.  Host and device events share one clock.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

DEVICE_PREFIX = '/device:TPU:'
OPS_LINE = 'XLA Ops'
MODULES_LINE = 'XLA Modules'
HOST_PLANE = '/host:CPU'


@dataclasses.dataclass
class Span:
    name: str
    start: float        # ns
    end: float          # ns
    stats: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Trace:
    ops: dict           # device plane name -> [Span] sorted by start
    modules: dict       # device plane name -> [Span] of program runs
    host: list          # [Span] of annotations whose name starts 'bench.'


def load(path: str) -> Trace:
    from jax._src.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, host = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    into = ops if line.name == OPS_LINE else modules
                    into[plane.name] = sorted(
                        (Span(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events), key=lambda s: s.start)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith('bench.'):
                        host.append(Span(e.name, e.start_ns,
                                         e.start_ns + e.duration_ns,
                                         {k: v for k, v in e.stats}))
    host.sort(key=lambda s: s.start)
    return Trace(ops=ops, modules=modules, host=host)


def op_name(span: Span) -> str:
    """The HLO instruction name: ``%fusion.12 = ...`` -> ``fusion.12``."""
    return span.name.split(' = ', 1)[0].lstrip('%')


def clip(spans, w0: float, w1: float) -> list:
    return [Span(s.name, max(s.start, w0), min(s.end, w1), s.stats)
            for s in spans if s.end > w0 and s.start < w1]


def union(spans) -> list:
    """Merged (start, end) intervals covered by ``spans``."""
    out: list = []
    for s in sorted(spans, key=lambda s: s.start):
        if out and s.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], s.end)
        else:
            out.append([s.start, s.end])
    return out


def busy_ns(spans, w0: float, w1: float) -> float:
    return sum(e - s for s, e in union(clip(spans, w0, w1)))


def gaps(spans, w0: float, w1: float) -> list:
    """(start, end) of every interval in [w0, w1] with no op running."""
    out, t = [], w0
    for s, e in union(clip(spans, w0, w1)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < w1:
        out.append((t, w1))
    return out


def self_times(spans, w0: float, w1: float) -> dict:
    """Op name -> time in [w0, w1] during which it ran and no op nested in
    it did (a loop's own time excludes its body's ops)."""
    spans = clip(spans, w0, w1)
    spans.sort(key=lambda s: (s.start, -s.end))
    out: dict = {}
    stack: list = []            # [span, time covered by its children]

    def close(entry):
        sp, child = entry
        name = op_name(sp)
        out[name] = out.get(name, 0.0) + (sp.end - sp.start) - child
        if stack:
            stack[-1][1] += sp.end - sp.start

    for sp in spans:
        while stack and stack[-1][0].end <= sp.start:
            close(stack.pop())
        stack.append([sp, 0.0])
    while stack:
        close(stack.pop())
    return out


def kernel_spans(spans, kernel: str, w0: float, w1: float) -> list:
    """Events of the Pallas kernel ``kernel``: instructions named
    ``<kernel>`` or ``<kernel>.<n>`` that are custom calls."""
    out = []
    for s in spans:
        if s.end <= w0 or s.start >= w1:
            continue
        name = op_name(s)
        if (name == kernel or name.startswith(kernel + '.')) \
                and 'custom-call(' in s.name:
            out.append(s)
    return out


def window(trace: Trace) -> Optional[tuple]:
    """(start, end) of the measured window: from the last data request for
    the step after the window's first to the end of the ``bench.window``
    span.  (The first step holds ``fit``'s second request for its first
    batch and the prefetcher's refill, a wait once per ``fit`` call.)"""
    win = [s for s in trace.host if s.name == 'bench.window']
    if not win:
        return None
    w = win[-1]
    data = [s for s in trace.host if s.name == 'bench.data'
            and w.start <= s.start <= w.end]
    if not data:
        return None
    second = int(data[0].stats.get('step', -1)) + 1
    starts = [s.start for s in data
              if int(s.stats.get('step', -2)) == second]
    if not starts:
        return None
    return starts[-1], w.end


def host_label(trace: Trace, t0: float, t1: float) -> str:
    """What the harness saw the host doing over most of [t0, t1]."""
    best, cover = 'host: step loop (dispatch, loss readback)', 0.0
    for s in trace.host:
        if s.name == 'bench.data':
            c = min(s.end, t1) - max(s.start, t0)
            if c > cover and c > 0.5 * (t1 - t0):
                best, cover = 'host: waiting for the next batch', c
    return best
