"""The optimizer's bucket plan of glm4-9b's one-chip share (4 layers, an
eighth of the untied vocabulary head), taken from ``jax.eval_shape``
parameters: no array is made.

Each layer weight is one scan-stacked leaf ``(4, d_in, d_out)``, so a
bucket of one shape holds one or two leaves.  That is below
``DEFAULT_MIN_BUCKET_SIZE``: no bucket is gathered into a stack, and Eva
takes the broadcast path over each leaf's leading layer axis.  The head
is a lone leaf of its own bucket, preconditioned all the same."""
import math

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core import bucketing, make_optimizer
from repro.models import build_model
from repro.models import module as M
from repro.obs import events
from repro.train.step import stats_plan_of

EVA = dict(lr=0.05, gamma=0.03, kv_decay=0.95, kl_kappa=1e-3, momentum=0.9,
           fused=False)


@pytest.fixture(scope='module')
def plan():
    model = build_model(get_config('glm4-9b').replace(n_layers=4,
                                                      vocab=18944))
    _, capture = make_optimizer('eva', **EVA)
    params = jax.eval_shape(lambda: M.init_params(model.param_specs(),
                                                  jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((3, 2048), jnp.int32)
    return stats_plan_of(model, capture, params,
                         {'tokens': tokens, 'labels': tokens})


def test_every_layer_weight_and_the_head_are_preconditioned(plan):
    got = {b.key: (b.paths, b.stacked) for b in plan.buckets}
    assert got == {
        'bfloat16_4x4096x4096': (('blocks/attn/o/w', 'blocks/attn/q/w'),
                                 False),
        'bfloat16_4x4096x256': (('blocks/attn/k/w', 'blocks/attn/v/w'),
                                False),
        'bfloat16_4x4096x13696': (('blocks/mlp/gate/w', 'blocks/mlp/up/w'),
                                  False),
        'bfloat16_4x13696x4096': (('blocks/mlp/down/w',), False),
        'bfloat16_4096x18944': (('lm_head/w',), False),
    }
    assert all(len(b.paths) < bucketing.DEFAULT_MIN_BUCKET_SIZE
               for b in plan.buckets)


def test_plan_record_counts_the_gradient_read(plan):
    body = events.plan_fields(plan)
    assert events.validate_record({'event': 'optimizer_plan', **body}) == []
    assert body['buckets']['bfloat16_4096x18944'] == {
        'leaves': 1, 'stacked': False, 'g_bytes': 4096 * 18944 * 2}
    # every matmul weight of the share, in bf16: all but the embedding
    # table and the norms' scales
    layer = 4096 * 4096 * 2 + 4096 * 256 * 2 + 4096 * 13696 * 3
    total = sum(b['g_bytes'] for b in body['buckets'].values())
    assert total == 2 * (4 * layer + 4096 * 18944)
    assert math.isclose(total / 1e9, 1.79, abs_tol=0.005)
