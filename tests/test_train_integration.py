"""Integration: trainer resume bit-exactness, preemption checkpoint,
compressed-DP parity, flash-vs-naive model equivalence."""
import os
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh

from repro.configs.registry import demo_lm
from repro.core.registry import make_optimizer
from repro.data.synthetic import LMStream
from repro.models import build_model
from repro.models import module as M
from repro.train import checkpoint as ckpt
from repro.train.compression import make_dp_train_step
from repro.train.step import init_opt_state, make_train_step
from repro.train.trainer import Trainer, TrainerConfig


def _setup():
    cfg = demo_lm('small')
    model = build_model(cfg)
    params = M.init_params(model.param_specs(), jax.random.PRNGKey(0))
    data = LMStream(vocab=cfg.vocab, seq_len=32, batch=8, seed=1)
    return cfg, model, params, data


def test_resume_bit_exact(tmp_path):
    cfg, model, params, data = _setup()
    opt, capture = make_optimizer('eva', lr=0.05)

    # uninterrupted 10 steps
    tc = TrainerConfig(total_steps=10, log_every=100, ckpt_every=0,
                       out_dir=str(tmp_path / 'a'))
    p_full, _, h_full = Trainer(model, opt, capture, tc).fit(params, data,
                                                             resume=False)

    # 5 steps + checkpoint, then resume for 5 more
    tc1 = TrainerConfig(total_steps=5, log_every=100, ckpt_every=5,
                        out_dir=str(tmp_path / 'b'))
    Trainer(model, opt, capture, tc1).fit(params, data, resume=False)
    tc2 = TrainerConfig(total_steps=10, log_every=100, ckpt_every=5,
                        out_dir=str(tmp_path / 'b'))
    p_res, _, h_res = Trainer(model, opt, capture, tc2).fit(params, data)

    np.testing.assert_allclose(np.asarray(h_res[-1]), np.asarray(h_full[-1]),
                               rtol=1e-5, atol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5),
        p_full, p_res)


def test_preemption_checkpoints_and_exits(tmp_path):
    cfg, model, params, data = _setup()
    opt, capture = make_optimizer('sgd', lr=0.05)
    tc = TrainerConfig(total_steps=1000, log_every=10_000, ckpt_every=0,
                       out_dir=str(tmp_path))
    tr = Trainer(model, opt, capture, tc)
    orig = tr.step_fn
    count = {'n': 0}

    def wrapped(*a):
        count['n'] += 1
        if count['n'] == 4:
            tr._preempted = True  # simulate SIGTERM delivery
        return orig(*a)

    tr.step_fn = wrapped
    tr.fit(params, data, resume=False)
    assert count['n'] == 4  # stopped promptly
    assert ckpt.latest_step(tmp_path / 'ckpt') == 4  # saved before exit


def test_compressed_dp_matches_uncompressed_closely():
    cfg, model, params, data = _setup()
    opt, capture = make_optimizer('eva', lr=0.05)
    mesh = make_mesh((1,), ('data',))
    losses = {}
    for compress in (False, True):
        step_fn, init_err = make_dp_train_step(model, opt, capture, mesh,
                                               compress=compress)
        st = init_opt_state(model, opt, capture, params, data.batch_at(0))
        err = init_err(params)
        p = params
        for i in range(8):
            p, st, err, m = step_fn(p, st, err, data.batch_at(i))
        losses[compress] = float(m['loss'])
    assert abs(losses[True] - losses[False]) / losses[False] < 0.05


def test_flash_config_matches_naive_loss():
    cfg, model, params, data = _setup()
    batch = data.batch_at(0)
    l1 = model.loss_fn(params, None, batch, None)[0]
    cfg2 = cfg.replace(attn_impl='flash', q_chunk=16, k_chunk=16)
    model2 = build_model(cfg2)
    l2 = model2.loss_fn(params, None, batch, None)[0]
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-4)
