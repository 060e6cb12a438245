"""The program's names for the parts of a step, as the benchmark reads them:
the ``op_name`` scopes of a compiled Eva step (``bench/scopes.py``), and
the readers of those parts and of the training loop's ``train.*`` spans,
against a small trace in the TPU profiler's layout
(``fixtures/trace_program.pbtxt``, its numbers worked out in its header,
its ops' metadata in ``fixtures/step_small.hlo``)."""
import dataclasses
import re
from pathlib import Path

import pytest

FIXTURES = Path(__file__).resolve().parent / 'fixtures'
DEV = '/device:TPU:0'
PROBED = ('forward_ms_per_step', 'backward_ms_per_step',
          'optimizer_ms_per_step', 'precondition_ms_per_step',
          'dispatch_ms', 'loop_host_ms')


# ---------------------------------------------------------------------------
# the names of a compiled step


def _fused_computations(hlo: str) -> dict:
    """Computation name -> the opcodes of its instructions."""
    out, comp = {}, None
    for line in hlo.splitlines():
        m = re.match(r'^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{$', line)
        if m:
            comp = m.group(1)
            out[comp] = []
            continue
        m = re.match(r'^\s+(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(?:\([^=]*?\)|\S+)'
                     r'\s+([\w-]+)\(', line)
        if m and comp:
            out[comp].append(m.group(1))
    return out


def _instructions(hlo: str, opcodes) -> list:
    """(name, opcode, called computation) of every instruction of those
    opcodes, in every computation."""
    pat = re.compile(r'^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(?:\([^=]*?\)|\S+)'
                     r'\s+(' + '|'.join(opcodes) + r')\((.*)$', re.M)
    out = []
    for m in pat.finditer(hlo):
        call = re.search(r'calls=%?([\w.\-]+)', m.group(3))
        out.append((m.group(1), m.group(2), call and call.group(1)))
    return out


def _tiny_step_hlo(fused: bool) -> str:
    """A qwen2-0.5b block at a test's size (scanned layers, ``remat='dots'``,
    flash attention), trained by Eva, compiled on the CPU."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core import make_optimizer
    from repro.models import build_model
    from repro.models import module as M
    from repro.train.step import init_opt_state, make_train_step
    arch = get_config('qwen2-0.5b').replace(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=128, vocab=512)
    assert arch.remat == 'dots' and arch.attn_impl == 'flash'
    model = build_model(arch)
    params = M.init_params(model.param_specs(), jax.random.PRNGKey(0))
    opt, capture = make_optimizer('eva', lr=0.05, fused=fused)
    tok = jnp.zeros((2, 64), jnp.int32)
    batch = {'tokens': tok, 'labels': tok}
    state = init_opt_state(model, opt, capture, params, batch)
    step = make_train_step(model, opt, capture)
    return jax.jit(step, donate_argnums=(0, 1)).lower(
        params, state, batch).compile().as_text()


@pytest.mark.parametrize('fused', [False, True], ids=['composed', 'fused'])
def test_compiled_eva_step_names_its_parts(fused):
    """Every dot of the step lies in a part of the step, every part of an
    Eva step is there, and what stays unscoped holds no dot: the compiler's
    own wrappers and the loop-invariant tables a scan's partial evaluation
    hoists out of the differentiated function."""
    from bench import scopes
    hlo = _tiny_step_hlo(fused)
    names = scopes.op_scopes(hlo)
    comps = _fused_computations(hlo)
    dots = _instructions(hlo, ('dot', 'convolution'))
    assert dots
    parts = scopes.STEP_SCOPES + scopes.OPTIMIZER_SCOPES
    for name, _, _ in dots:
        assert scopes.scope_of(names.get(name)) in parts, \
            (name, names.get(name))
    seen = {scopes.scope_of(names.get(n)) for n, _, _ in
            _instructions(hlo, ('fusion', 'dot', 'convolution'))}
    assert {'forward', 'backward', 'capture', 'kv', 'precondition',
            'kl_clip', 'apply', 'metrics'} <= seen
    for name, _, called in _instructions(hlo, ('fusion',)):
        if scopes.scope_of(names.get(name)) == scopes.UNSCOPED:
            assert not {'dot', 'convolution', 'custom-call'} & set(
                comps.get(called, [])), (name, names.get(name))


def test_dp_step_names_its_exchange():
    """The explicit data-parallel step's gradient and statistics means are
    the ``exchange`` part."""
    import jax
    import jax.numpy as jnp
    from repro.configs.registry import demo_lm
    from repro.core import make_optimizer
    from repro.launch.mesh import make_data_mesh
    from repro.models import build_model
    from repro.models import module as M
    from repro.train.step import init_opt_state, make_dp_step
    from bench import scopes
    cfg = demo_lm('small')
    model = build_model(cfg)
    params = M.init_params(model.param_specs(), jax.random.PRNGKey(0))
    opt, capture = make_optimizer('eva', lr=0.05)
    tok = jnp.zeros((2, 8), jnp.int32)
    batch = {'tokens': tok, 'labels': tok}
    state = init_opt_state(model, opt, capture, params, batch)
    step = make_dp_step(model, opt, capture, make_data_mesh(1))
    hlo = jax.jit(step).lower(params, state, batch).compile().as_text()
    seen = {scopes.scope_of(v) for v in scopes.op_scopes(hlo).values()}
    assert {'exchange', 'forward', 'backward', 'precondition'} <= seen


@pytest.mark.parametrize('op_name,part', [
    ('jit(train_step)/jvp(forward)/while/body/closed_call/dot_general',
     'forward'),
    ('jit(train_step)/transpose(jvp(forward))/while/body/dot_general',
     'backward'),
    ('jit(train_step)/transpose(jvp(forward))/jvp(forward)/checkpoint/'
     'rematted_computation/mul', 'backward'),
    ('jit(train_step)/capture/div', 'capture'),
    ('jit(train_step)/optimizer/mul', 'optimizer'),
    ('jit(train_step)/optimizer/kv/add', 'kv'),
    ('jit(train_step)/optimizer/precondition/jit(eva_fused_stacked)/mul',
     'precondition'),
    ('jit(train_step)/optimizer/kl_clip/sqrt', 'kl_clip'),
    ('jit(train_step)/apply/add', 'apply'),
    ('jit(train_step)/metrics/reduce_sum', 'metrics'),
    ('jit(local_step)/shmap_body/exchange/psum', 'exchange'),
    ('jit(train_step)/closed_call/while/body/dynamic_update_slice',
     'unscoped'),
    ('checkpoint/rematted_computation/reduce_sum', 'unscoped'),
    ('reduce_sum', 'unscoped'),
    (None, 'unscoped'),
])
def test_scope_of(op_name, part):
    from bench import scopes
    assert scopes.scope_of(op_name) == part


def test_op_scopes_reads_the_metadata():
    """Instructions with metadata map to their ``op_name``, in every
    computation; an async copy the compiler made takes its operand's."""
    from bench import scopes
    names = scopes.op_scopes((FIXTURES / 'step_small.hlo').read_text())
    assert names['while.1'] == 'jit(train_step)/jvp(forward)/while'
    assert names['fusion.2'] == 'jit(train_step)/jvp(forward)/while/body/add'
    assert names['eva_fused_stacked.5'].endswith('jit(eva_fused_stacked)/mul')
    assert names['fusion.7'] == 'jit(train_step)/apply/add'
    assert names['reduce.6'] == 'reduce_sum'
    assert names['copy-start.4'] == names['convolution.3']
    assert 'tuple.3' not in names           # the loop body's result: no reader


def test_op_scopes_names_what_the_compiler_made():
    """Without metadata: a fusion takes its computation's root's name, a
    copy its operand's, a broadcast of a constant its user's."""
    from bench import scopes
    hlo = ('%fused_computation.1 (p.0: f32[4]) -> f32[4] {\n'
           '  %p.0 = f32[4]{0} parameter(0)\n'
           '  ROOT %mul.1 = f32[4]{0:T(256)} multiply(f32[4]{0} %p.0, '
           'f32[4]{0} %p.0), metadata={op_name="jit(train_step)/optimizer/'
           'kl_clip/mul"}\n'
           '}\n\n'
           'ENTRY %main.2 (x.1: f32[4]) -> (f32[4], f32[4]) {\n'
           '  %x.1 = f32[4]{0} parameter(0)\n'
           '  %constant.3 = f32[] constant(0)\n'
           '  %broadcast.4 = f32[4]{0:T(256)} broadcast(f32[] %constant.3), '
           'dimensions={}\n'
           '  %wrapped_multiply = f32[4]{0:T(256)} fusion(f32[4]{0} %x.1), '
           'kind=kLoop, calls=%fused_computation.1\n'
           '  %copy.5 = f32[4]{0:T(256)} copy(f32[4]{0} %wrapped_multiply)\n'
           '  %add.6 = f32[4]{0} add(f32[4]{0} %broadcast.4, f32[4]{0} '
           '%copy.5), metadata={op_name="jit(train_step)/apply/add"}\n'
           '  ROOT %tuple.7 = (f32[4]{0}, /*index=1*/f32[4]{0}) tuple('
           '%add.6, %copy.5)\n'
           '}\n')
    names = scopes.op_scopes(hlo)
    assert {n: scopes.scope_of(names.get(n)) for n in (
        'wrapped_multiply', 'copy.5', 'broadcast.4', 'constant.3', 'add.6',
        'x.1')} == {
        'wrapped_multiply': 'kl_clip', 'copy.5': 'kl_clip',
        'broadcast.4': 'apply', 'constant.3': 'apply', 'add.6': 'apply',
        'x.1': 'kl_clip'}


# ---------------------------------------------------------------------------
# the readers, on the program's trace


@pytest.fixture
def program_trace(tmp_path):
    from jax._src.profiler import ProfileData
    from bench import scopes, trace
    path = tmp_path / 'program.xplane.pb'
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        (FIXTURES / 'trace_program.pbtxt').read_text()))
    return (trace.load(str(path)), scopes.program_spans(str(path)),
            scopes.op_scopes((FIXTURES / 'step_small.hlo').read_text()))


def test_scope_times_count_a_loop_once(program_trace):
    from bench import scopes, trace
    tr, _, names = program_trace
    w0, w1 = trace.window(tr)
    assert (w0, w1) == (10000.0, 100000.0)
    assert scopes.scope_times(tr.ops[DEV], w0, w1, names) == {
        'forward': 10000.0, 'precondition': 20000.0, 'backward': 26000.0,
        'apply': 4000.0}
    assert scopes.scope_times(tr.ops[DEV], w0, w1, {}) == {
        'unscoped': 60000.0}


def test_program_spans_are_the_loops_own(program_trace):
    _, spans, _ = program_trace
    assert [s.name for s in spans[:5]] == [
        'train', 'train.data', 'train.dispatch', 'train.wait', 'train.host']
    steps = [s for s in spans if s.name == 'train']
    assert [int(s.stats['step_num']) for s in steps] == [4, 5, 6]


@dataclasses.dataclass
class NamedView:
    """What ``harness.View`` would carry with the program's names: the
    compiled step's ``op_scopes`` and the window's ``train.*`` spans."""
    op_scopes: dict = None
    program: list = None


def _view(program_trace, **kw):
    from bench import harness, trace
    tr, spans, names = program_trace
    w0, w1 = trace.window(tr)
    cell = harness.load_cell('qwen2-0.5b.eva-fused.b1s2048')
    args = dict(
        cell=cell, peaks=harness.read_peaks('TPU v5 lite'), chips=1,
        steps=2, window_s=(w1 - w0) / 1e9,
        busy_s=trace.busy_ns(tr.ops[DEV], w0, w1) / 1e9,
        ops=[tr.ops[DEV]], modules=[tr.modules[DEV]], w0=w0, w1=w1,
        data_wait_s=[0.002, 0.013], hbm_peak_bytes=None, flops_per_token=1.0,
        op_scopes=names, program=spans)
    args.update(kw)
    named = dataclasses.make_dataclass(
        'Named', [], bases=(NamedView, harness.View))
    return cell, named(**args)


@pytest.mark.parametrize('name,ms', [
    ('forward_ms_per_step', 0.005), ('backward_ms_per_step', 0.013),
    ('optimizer_ms_per_step', 0.012), ('precondition_ms_per_step', 0.010),
    ('dispatch_ms', 0.00075), ('loop_host_ms', 0.00605)])
def test_probed_reader_on_the_program_trace(program_trace, name, ms):
    cell, view = _view(program_trace)
    assert cell.metric_reader(name).read(view) == pytest.approx(ms)


@pytest.mark.parametrize('name', PROBED)
def test_probed_reader_reads_nothing_without_names(program_trace, name):
    """A view without the program's names (the harness's as it is, or a
    program without scopes and spans) reads nothing and does not raise."""
    from bench import harness
    cell, named = _view(program_trace)
    plain = harness.View(**{f.name: getattr(named, f.name)
                            for f in dataclasses.fields(harness.View)})
    assert cell.metric_reader(name).read(plain) is None
    _, empty = _view(program_trace, op_scopes={}, program=[])
    assert cell.metric_reader(name).read(empty) is None
    _, idle = _view(program_trace, ops=[[]], program=[])
    assert cell.metric_reader(name).read(idle) is None


def test_program_label_names_the_gaps(program_trace):
    """Each idle gap is named by the loop's span covering most of it; the
    harness's own labels remain the fallback."""
    from bench import scopes, trace
    tr, spans, _ = program_trace
    w0, w1 = trace.window(tr)
    gaps = trace.gaps(tr.ops[DEV], w0, w1)
    assert gaps == [(10000.0, 12000.0), (42000.0, 58000.0),
                    (88000.0, 100000.0)]
    assert [scopes.program_label(spans, a, b) for a, b in gaps] == [
        'train.data', 'train.data', 'train.host']
    assert scopes.program_label([], 10000.0, 12000.0) is None


def test_probe_summary_on_the_program_trace(program_trace):
    """What ``bench/scope_probe.py`` prints beside the readers: device ms per
    part and the scoped share, the traced steps' median, the loop's span
    times and where the device's idle time fell among them."""
    from bench import scope_probe
    tr, spans, names = program_trace
    got = scope_probe.summary({'trace': tr, 'scopes': names,
                               'program': spans})
    assert got['scope_ms_per_step'] == pytest.approx({
        'forward': 0.005, 'precondition': 0.010, 'backward': 0.013,
        'apply': 0.002})
    assert got['scoped_share'] == 1.0 and got['steps'] == 2
    assert got['step_ms_median'] == pytest.approx(0.034)
    assert got['program_spans_ms'] == pytest.approx({
        'train.data': 0.01315, 'train.dispatch': 0.00075,
        'train.wait': 0.0304, 'train.host': 0.00605})
    assert got['gap_split_ms'] == pytest.approx({
        'outside spans': 0.001175, 'train.data': 0.006575,
        'train.dispatch': 0.00025, 'train.host': 0.00605,
        'train.wait.head': 0.0002, 'train.wait.tail': 0.00075})
    assert got['unscoped_top'] == []
