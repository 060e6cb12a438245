"""Versioned, schema-typed telemetry records + the JSONL ``Recorder``.

One event model for everything the runtime emits — trainer step records,
refresh/ownership/comm-exchange one-offs, the optimizer's bucket plan,
straggler flags, phase spans, the loop's read-back counts and profile
samples — replacing the hand-rolled dicts that used to be scattered
across ``train/trainer.py``, ``comm/metrics.py`` and the benchmarks.

Design rules:

* Every record is one JSON object per line with an ``event`` type and a
  schema version ``v`` (``SCHEMA_VERSION``).  Everything else is typed by
  ``SCHEMAS[event]``; per-site key families use a trailing ``/*``
  (``pipeline_lag/stats/kfac``).  Unknown top-level keys are validation
  errors — the emitters are all in-repo, so strictness catches typos
  instead of letting them rot in artifacts.
* Records are **bit-compatible supersets** of the pre-obs trainer fields:
  old parsers that read ``step``/``loss``/``step_time_s`` keep working,
  and the loader treats envelope-less step-shaped dicts as legacy ``step``
  records (pre-v1 files stay readable).
* Versioning policy: bump ``SCHEMA_VERSION`` whenever a field changes
  name, unit, or type, or a required field is added — adding an optional
  field is NOT a bump (supersets are the compatibility contract).  Note
  the bump in CHANGES.md (see the conventions block there).
* The scheduler-owned step fields come from the producing modules'
  ``METRIC_FIELDS`` declarations (``schedule/runtime.py``,
  ``schedule/pipeline.py``) so the schema cannot drift from the code that
  emits them.

The ``Recorder`` owns the sink AND the run-scoped comm-counter context
(``repro.comm.metrics.scope``): while a recorder is open, every exchange
site traced belongs to *its* run — this replaces the trainer's old
trace-count-baselining workaround over the process-global table.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from typing import Any, Optional

from repro.comm import metrics as comm_metrics
from repro.core import factor_sharded as _fsh
from repro.schedule import pipeline as _pipemod
from repro.schedule import runtime as _schedrt

SCHEMA_VERSION = 1

_NUM = (int, float)
_INT = (int,)
_STR = (str,)
_DICT = (dict,)
_BOOL = (bool,)


@dataclasses.dataclass(frozen=True)
class Field:
    """One schema field: accepted JSON types, requiredness, display unit."""
    types: tuple
    required: bool = False
    unit: str = ''


def _declared(module) -> dict[str, 'Field']:
    """METRIC_FIELDS of a producer module -> schema fields."""
    kinds = {'int': _INT, 'num': _NUM}
    return {name: Field(kinds[kind], unit=unit)
            for name, (kind, unit) in module.METRIC_FIELDS.items()}


SCHEMAS: dict[str, dict[str, Field]] = {
    # one per logged training step (superset of the pre-obs record)
    'step': {
        'step': Field(_INT, required=True, unit='index'),
        'loss': Field(_NUM, required=True),
        'grad_norm': Field(_NUM),
        'step_time_s': Field(_NUM, unit='s'),
        'exchanged_mb_cum': Field(_NUM, unit='MiB'),
        **_declared(_schedrt),
        **_declared(_pipemod),
        **_declared(_fsh),
    },
    # one per realized curvature refresh (derived from the cumulative
    # counter crossing between steps)
    'refresh': {
        'step': Field(_INT, required=True, unit='index'),
        'refreshes': Field(_INT, required=True, unit='cumulative refreshes'),
        'step_time_s': Field(_NUM, unit='s'),
    },
    # startup one-off: per-bucket refresh-owner map
    'refresh_ownership': {
        'world': Field(_INT, required=True, unit='workers'),
        'owners': Field(_DICT, required=True,
                        unit='bucket -> per-worker slice counts'),
    },
    # elastic resize one-off: a checkpoint written at world_from resumed
    # at world_to (or a live between-steps resize) — emitted by
    # Trainer.fit_elastic after schedule.reshard.reshard_state (optional
    # event type: no version bump)
    'reshard': {
        'world_from': Field(_INT, required=True, unit='workers'),
        'world_to': Field(_INT, required=True, unit='workers'),
        'pipeline': Field(_STR, required=True,
                          unit="in-flight buffers: 'drained'|'kept'|'none'"),
        'source': Field(_STR, required=True,
                        unit="'checkpoint' (restore) | 'live' (between steps)"),
        'step': Field(_INT, unit='index'),
        'slices_total': Field(_INT, unit='owned refresh slices'),
        'slices_moved': Field(_INT, unit='slices with a new owner'),
    },
    # post-trace one-off: per-call-site logical exchange bytes (site dicts
    # are validated by _validate_nested; codec extras stay open)
    'comm_exchange': {
        'sites': Field(_DICT, required=True),
    },
    # straggler watchdog flag
    'straggler': {
        'step': Field(_INT, required=True, unit='index'),
        'step_time_s': Field(_NUM, required=True, unit='s'),
        'median_s': Field(_NUM, required=True, unit='s'),
        'factor': Field(_NUM, unit='trigger threshold x median'),
    },
    # one host span of the training loop (profile mode): data, dispatch,
    # wait (the step's read-back) or host; nothing waits on the device for
    # it
    'span': {
        'name': Field(_STR, required=True),
        'ms': Field(_NUM, required=True, unit='ms'),
        'step': Field(_INT, unit='index'),
        'seq': Field(_INT, unit='emission order'),
        'depth': Field(_INT, unit='nesting depth'),
        'parent': Field(_STR + (type(None),)),
    },
    # one per Trainer.fit call: its read-backs, made with the next step
    # already dispatched (overlapped) or with nothing queued (drained)
    'loop': {
        'steps': Field(_INT, required=True, unit='steps read back'),
        'overlapped': Field(_INT, required=True,
                            unit='read-backs with the next step queued'),
        'drained': Field(_INT, required=True,
                         unit='read-backs with nothing queued'),
    },
    # one per Trainer.fit call, before its loop: the optimizer's bucket
    # plan (bucket dicts are validated by _validate_nested)
    'optimizer_plan': {
        'buckets': Field(_DICT, required=True,
                         unit='bucket key -> {leaves, stacked, g_bytes}'),
    },
    # one per trace of Trainer.fit's step: the path of each attention call
    # site the step traced (site dicts are validated by _validate_nested)
    'attention_plan': {
        'sites': Field(_DICT, required=True,
                       unit='site -> {path, rule, block_q, block_k, tiles, '
                            'tiles_total}'),
    },
    # profile-mode sample: live buffers + one-shot HLO costs of the step
    'profile': {
        'step': Field(_INT, required=True, unit='index'),
        'live_buffer_mb': Field(_NUM, unit='MiB'),
        'device_bytes_in_use': Field(_INT, unit='bytes'),
        'fns': Field(_DICT, unit='fn -> HLO cost/overlap summary'),
    },
    # one BENCH_*.json row (benchmarks/common.write_json)
    'bench': {
        'name': Field(_STR, required=True),
        'us_per_call': Field(_NUM, required=True, unit='us'),
        'derived': Field(_STR),
        'fields': Field(_DICT),
    },
}

_SITE_FIELDS = {
    'bytes_per_call': Field(_INT, required=True, unit='B'),
    'codec': Field(_STR, required=True),
    'mode': Field(_STR, required=True),
    'traces': Field(_INT),
    'world': Field(_INT),
    'pods': Field((list, tuple), unit='(n_pods, pod_size)'),
    'ici_bytes': Field(_INT, unit='B'),
    'dcn_bytes': Field(_INT, unit='B'),
    # sharded-factor apply sites (factor/*) — optional, no version bump
    'solve_iters': Field(_INT, unit='iterations per solve'),
    'factor_shard_bytes': Field(_INT, unit='B of factor band per worker'),
}


_BUCKET_FIELDS = {
    'leaves': Field(_INT, required=True, unit='parameter leaves'),
    'stacked': Field(_BOOL, required=True,
                     unit='gathered into one stack for the update'),
    'g_bytes': Field(_INT, required=True, unit='B of gradient read per step'),
}

_ATTENTION_FIELDS = {
    'path': Field(_STR, required=True, unit="'pallas' | 'xla'"),
    'rule': Field(_STR, required=True, unit='why this path'),
    'block_q': Field(_INT, required=True, unit='query rows per tile'),
    'block_k': Field(_INT, required=True, unit='key rows per tile'),
    'tiles': Field(_INT, required=True, unit='tiles computed per head'),
    'tiles_total': Field(_INT, required=True, unit='tiles of S x S per head'),
}

# events whose one dict field holds records of their own: (field, schema)
_NESTED = {'comm_exchange': ('sites', _SITE_FIELDS),
           'optimizer_plan': ('buckets', _BUCKET_FIELDS),
           'attention_plan': ('sites', _ATTENTION_FIELDS)}


class SchemaError(ValueError):
    pass


def _check(value, fld: Field, where: str) -> list[str]:
    # bool is an int subclass in Python; never a valid numeric field here
    if (isinstance(value, bool) and bool not in fld.types) \
            or not isinstance(value, fld.types):
        return [f'{where}: expected {"/".join(t.__name__ for t in fld.types)}'
                f', got {type(value).__name__} ({value!r})']
    return []


def _validate_nested(where: str, rec: Any, fields: dict) -> list[str]:
    if not isinstance(rec, dict):
        return [f'{where}: expected object, got {type(rec).__name__}']
    errs = []
    for name, fld in fields.items():
        if name in rec:
            errs += _check(rec[name], fld, f'{where}.{name}')
        elif fld.required:
            errs.append(f'{where}: missing required field {name!r}')
    return errs  # codec/topology extras beyond the fields stay open


def infer_event(rec: dict) -> Optional[str]:
    """Event type of a record; legacy envelope-less step dicts count."""
    ev = rec.get('event')
    if ev is None and 'step' in rec and 'loss' in rec:
        return 'step'
    return ev


def validate_record(rec: Any) -> list[str]:
    """All schema violations of one record ([] = valid)."""
    if not isinstance(rec, dict):
        return [f'record is not an object: {rec!r}']
    ev = infer_event(rec)
    if ev is None:
        return [f'missing event type (keys: {sorted(rec)[:6]})']
    if ev not in SCHEMAS:
        return [f'unknown event type {ev!r} (have {sorted(SCHEMAS)})']
    errs: list[str] = []
    v = rec.get('v')
    if v is not None and v != SCHEMA_VERSION:
        errs.append(f'{ev}: schema version {v} != {SCHEMA_VERSION}')
    schema = SCHEMAS[ev]
    for name, fld in schema.items():
        if fld.required and name not in rec:
            errs.append(f'{ev}: missing required field {name!r}')
    for key, value in rec.items():
        if key in ('event', 'v'):
            continue
        fld = schema.get(key)
        if fld is None and '/' in key:
            fld = schema.get(key.split('/', 1)[0] + '/*')
        if fld is None:
            errs.append(f'{ev}: unknown field {key!r}')
            continue
        errs += _check(value, fld, f'{ev}.{key}')
    field, fields = _NESTED.get(ev, (None, None))
    if field is not None and isinstance(rec.get(field), dict):
        for key, sub in rec[field].items():
            errs += _validate_nested(f'{ev}.{field}[{key!r}]', sub, fields)
    return errs


def step_fields(metrics: dict) -> dict:
    """Typed host-side step-record fields from the jitted step's metrics
    dict, as read back to the host (``Trainer.fit``) or still on the
    device (each conversion then waits on it)."""
    out: dict[str, Any] = {}
    if 'refreshes' in metrics:
        out['refreshes'] = int(metrics['refreshes'])
        out['staleness'] = float(metrics['staleness'])
        out['refresh_since'] = int(metrics['refresh_since'])
    for key, value in metrics.items():
        if key.startswith('pipeline_lag'):
            out[key] = int(value)
    if 'factor_solve_iters' in metrics:
        out['factor_solve_iters'] = int(metrics['factor_solve_iters'])
        out['factor_shard_bytes'] = float(metrics['factor_shard_bytes'])
    return out


def plan_fields(plan) -> dict:
    """The ``optimizer_plan`` record body of a bucket plan
    (``core.bucketing.BucketPlan``): per bucket its leaf count, whether the
    update stacks it, and the bytes of gradient it reads per step."""
    return {'buckets': {
        b.key: {'leaves': len(b.paths), 'stacked': b.stacked,
                'g_bytes': len(b.paths) * math.prod(b.shape)
                * b.dtype.itemsize}
        for b in plan.buckets}}


def attention_fields(plans) -> dict:
    """The ``attention_plan`` record body of ``models.flash.AttentionPlan``
    entries, one per call site (a site traced twice keeps its last)."""
    return {'sites': {p.site: {'path': p.path, 'rule': p.rule,
                               'block_q': p.block_q, 'block_k': p.block_k,
                               'tiles': p.tiles,
                               'tiles_total': p.tiles_total}
                      for p in plans}}


class Recorder:
    """JSONL sink + run-scoped comm-counter context.

    ``emit`` stamps the envelope (``event``, ``v``), validates against the
    schema (fail-fast — a malformed record is a bug at the emit site, not
    something to discover in the artifact), appends one line, and returns
    the record.  ``path=None`` keeps records in memory only (tests).
    """

    def __init__(self, path: Optional[Any] = None, validate: bool = True,
                 scope_comm: bool = True):
        self._f = Path(path).open('a') if path is not None else None
        self._validate = validate
        self._scope = comm_metrics.push_scope() if scope_comm else None
        self.records: list[dict] = []

    def emit(self, event: str, **fields: Any) -> dict:
        rec = {'event': event, 'v': SCHEMA_VERSION, **fields}
        if self._validate:
            errs = validate_record(rec)
            if errs:
                raise SchemaError('; '.join(errs))
        self.records.append(rec)
        if self._f is not None:
            self._f.write(json.dumps(rec) + '\n')
            self._f.flush()
        return rec

    def comm_sites(self) -> dict:
        """Exchange sites traced while THIS recorder was open (falls back
        to the process-global table when scoping was disabled)."""
        if self._scope is not None:
            return self._scope.snapshot()
        return comm_metrics.snapshot()

    def close(self) -> None:
        if self._scope is not None:
            comm_metrics.pop_scope(self._scope)
            self._scope = None
        if self._f is not None:
            self._f.close()
            self._f = None

    def __enter__(self) -> 'Recorder':
        return self

    def __exit__(self, *exc) -> None:
        self.close()
