"""The program's own names for the parts of a traced training step.

The device ops of the compiled step carry the ``jax.named_scope`` path of
the code that emitted them in their HLO metadata (``op_name``):
``jit(train_step)/jvp(forward)/...`` for the forward pass,
``.../transpose(jvp(forward))/...`` for the backward pass (``remat``
recompute included), ``.../optimizer/precondition/...`` for Eva's
preconditioning, and so on.  ``op_scopes`` reads that map from the compiled
step's text (``compiled.as_text()``), ``scope_of`` names the part an
``op_name`` belongs to, and ``scope_times`` sums the device time of the
trace's ops by part.  The host side is the training loop's
``TraceAnnotation`` spans, named ``train.<phase>``; ``program_spans`` reads
them from a trace and ``program_label`` names an idle gap by them.

Nothing here runs inside the timed window: the HLO text comes from the
compile that follows it, the spans from the trace file.
"""
from __future__ import annotations

import re
from typing import Optional

from bench import trace as tr

# the parts of a step, in the order a report lists them; the optimizer's
# inner scopes (``OPTIMIZER_SCOPES``) are parts of ``optimizer``, and an op
# in none of them is ``UNSCOPED``
STEP_SCOPES = ('forward', 'backward', 'capture', 'optimizer', 'apply',
               'metrics', 'exchange')
OPTIMIZER_SCOPES = ('kv', 'precondition', 'kl_clip')
UNSCOPED = 'unscoped'
PROGRAM_PREFIX = 'train.'

_COMPUTATION = re.compile(r'^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{$')
# ``  [ROOT] %name = <shape> <opcode>(<operands>)[, <attribute>=...]``: the
# opcode is the first lowercase word after a space that opens a bracket
_INSTRUCTION = re.compile(r'^\s+(ROOT\s+)?%?([\w.\-]+)\s*=\s*.*?'
                          r'\s([a-z][\w\-]*)\((.*?)\)(?:,\s*\w+=|\s*$)')
# compiler-made instructions that move or re-lay one operand's data
_MOVES = frozenset(('copy', 'copy-start', 'copy-done', 'slice-start',
                    'slice-done', 'reshape', 'bitcast', 'transpose',
                    'convert', 'fusion'))
_OPERAND = re.compile(r'%([\w.\-]+)')
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r'calls=%?([\w.\-]+)')


def op_scopes(hlo_text: str) -> dict:
    """HLO instruction name -> its ``op_name`` metadata, over every
    computation of a compiled program's text.

    An instruction the compiler made without metadata takes the name of
    what it works on: a fusion that of the computation it calls (its
    root's, else its first named instruction's); one that moves or re-lays
    data (a copy, a reshape, the end of an async copy) that of its first
    named operand; any other (a broadcast of a constant, a loop's initial
    tuple) that of its first named user."""
    named, comp_name, order = {}, {}, []
    comp = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m or comp is None:
            continue
        root, name, opcode, operands = m.groups()
        op = _OP_NAME.search(line)
        call = _CALLS.search(line)
        if op:
            named[name] = op.group(1)
            if root or comp not in comp_name:
                comp_name[comp] = op.group(1)
        order.append((name, opcode, call and call.group(1),
                      _OPERAND.findall(operands)))
    for name, _, called, _ in order:        # fusions: what they call
        if name not in named and called in comp_name:
            named[name] = comp_name[called]
    for name, opcode, _, operands in order:     # moves: what they read
        if name not in named and opcode in _MOVES:
            found = [a for a in operands if a in named]
            if found:
                named[name] = named[found[0]]
    for name, _, _, operands in reversed(order):    # the rest: who reads
        if name in named:
            for a in operands:
                named.setdefault(a, named[name])
    return named


def scope_of(op_name: Optional[str]) -> str:
    """The part of the step an ``op_name`` belongs to: one of
    ``STEP_SCOPES``, an optimizer scope of ``OPTIMIZER_SCOPES`` (the
    innermost), or ``'unscoped'`` (no ``jit(...)`` prefix, or no scope of
    the step's on its path)."""
    if not op_name or not op_name.startswith('jit('):
        return UNSCOPED
    parts = op_name.split('/')
    if 'transpose(jvp(forward))' in parts:
        return 'backward'
    if 'jvp(forward)' in parts or 'forward' in parts:
        return 'forward'
    if 'optimizer' in parts:
        inner = [p for p in parts if p in OPTIMIZER_SCOPES]
        return inner[-1] if inner else 'optimizer'
    for p in reversed(parts):
        if p in STEP_SCOPES:
            return p
    return UNSCOPED


def scope_times(ops, w0: float, w1: float, scopes: dict) -> dict:
    """Device time (ns) in [w0, w1] by ``scope_of`` part, from each op's
    self time (``trace.self_times``), so a loop's body counts once.  Ops
    the map does not name are ``'unscoped'``."""
    out: dict = {}
    for name, t in tr.self_times(ops, w0, w1).items():
        part = scope_of(scopes.get(name))
        out[part] = out.get(part, 0.0) + t
    return out


def program_spans(path: str) -> list:
    """The training loop's ``train.*`` host spans (and its ``train`` step
    events) of a trace file, sorted by start."""
    from jax._src.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != tr.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == 'train' or e.name.startswith(PROGRAM_PREFIX):
                    out.append(tr.Span(e.name, e.start_ns,
                                       e.start_ns + e.duration_ns,
                                       {k: v for k, v in e.stats}))
    out.sort(key=lambda s: s.start)
    return out


def span_ms(spans, name: str, w0: float, w1: float) -> list:
    """Durations (ms) of the spans called ``name`` that start in
    [w0, w1]."""
    return [(s.end - s.start) / 1e6 for s in spans
            if s.name == name and w0 <= s.start < w1]


def program_label(spans, t0: float, t1: float) -> Optional[str]:
    """The ``train.*`` span covering more than half of [t0, t1], if any."""
    best, cover = None, 0.0
    for s in spans:
        if not s.name.startswith(PROGRAM_PREFIX):
            continue
        c = min(s.end, t1) - max(s.start, t0)
        if c > cover and c > 0.5 * (t1 - t0):
            best, cover = s.name, c
    return best


# ---------------------------------------------------------------------------
# what the per-layer readers of these parts read from a ``harness.View``
# that carries ``op_scopes`` (the compiled step's map) and ``program`` (the
# window's ``train.*`` spans); a view without them reads nothing


def ms_per_step(view, parts) -> Optional[float]:
    """Device time (ms) per traced step of the ``scope_of`` parts in
    ``parts``, averaged over the chips used; None where no op of the
    window falls in them."""
    names = getattr(view, 'op_scopes', None)
    if not names or not view.steps or not view.ops:
        return None
    total = 0.0
    for ops in view.ops:
        times = scope_times(ops, view.w0, view.w1, names)
        total += sum(times.get(p, 0.0) for p in parts)
    if total <= 0:
        return None
    return total / len(view.ops) / 1e6 / view.steps


def mean_span_ms(view, name: str) -> Optional[float]:
    """Mean duration (ms) of the program's ``name`` spans that start in
    the traced window; None where there are none."""
    ms = span_ms(getattr(view, 'program', None) or [], name, view.w0,
                 view.w1)
    return sum(ms) / len(ms) if ms else None

