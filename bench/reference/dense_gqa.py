"""Plain float32 reference of a dense GQA decoder LM and its training steps.

Independent of the program: it imports nothing from it and takes nothing
that the program made.  Weights come from :func:`init_weights` (the
benchmark's own generator, from the seed), batches from ``bench.traffic``;
the optimizer's update is a ``bench/optimizers/<name>.py`` reference, named
by the cell's traffic file.

The block, as the program's dense family runs it (and as the config files
note where that departs from a published model): RMSNorm (f32, epsilon
``norm_eps``) -> Q/K/V projections (optional bias) -> rotate-half RoPE over
the whole head -> causal softmax attention, query head h reading KV head
h // (n_heads / n_kv_heads) -> output projection -> residual -> RMSNorm ->
SwiGLU MLP -> residual.  Final RMSNorm, then the untied head or the
transposed embedding, then mean token cross-entropy.  Parameters are stored
in the configuration's parameter dtype, all arithmetic is float32 at
``highest`` matmul precision.

Memory: the step runs layer by layer.  The forward keeps each layer's input;
the backward recomputes one layer at a time (``jax.vjp``) and folds its
gradient into the optimizer's state at once, so no gradient tree is ever
whole.  Attention runs one batch row at a time under ``jax.checkpoint``; the
head and its cross-entropy run over blocks of tokens.

``precision='fp8'`` is the control: every matmul takes e4m3 operands in the
forward pass and an e5m2 output cotangent in the backward pass, each with a
per-tensor scale, and accumulates in float32 -- the fp8 training recipe, the
nearest precision below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

PRECON = ('attn/q', 'attn/k', 'attn/v', 'attn/o', 'mlp/gate', 'mlp/up',
          'mlp/down')
HEAD_TOKENS = 1024      # tokens per block of the head's cross-entropy


# ---------------------------------------------------------------------------
# layout, weights, FLOPs


def param_layout(cfg: dict) -> dict:
    """'/'-path -> (shape, init) in the layout the program's dense LM takes."""
    L, D, H, KV = cfg['n_layers'], cfg['d_model'], cfg['n_heads'], cfg['n_kv_heads']
    dh, F, V = cfg['head_dim'], cfg['d_ff'], cfg['vocab']
    out = {'embed/table': ((V, D), 'embed')}
    lin = {'attn/q': (D, H * dh), 'attn/k': (D, KV * dh), 'attn/v': (D, KV * dh),
           'attn/o': (H * dh, D), 'mlp/gate': (D, F), 'mlp/up': (D, F),
           'mlp/down': (F, D)}
    for name, (i, o) in lin.items():
        out[f'blocks/{name}/w'] = ((L, i, o), 'fan_in')
        if cfg['qkv_bias'] and name in ('attn/q', 'attn/k', 'attn/v'):
            out[f'blocks/{name}/b'] = ((L, o), 'zeros')
    out['blocks/norm1/scale'] = ((L, D), 'ones')
    out['blocks/norm2/scale'] = ((L, D), 'ones')
    out['norm_f/scale'] = ((D,), 'ones')
    if not cfg['tie_embeddings']:
        out['lm_head/w'] = ((D, V), 'fan_in')
    return out


def seed_key(seed: int):
    """A PRNG key from any whole number up to 64 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _unflatten(flat: dict) -> dict:
    out: dict = {}
    for path, leaf in flat.items():
        d = out
        keys = path.split('/')
        for k in keys[:-1]:
            d = d.setdefault(k, {})
        d[keys[-1]] = leaf
    return out


def flatten(tree, prefix: str = '') -> dict:
    if isinstance(tree, dict):
        out = {}
        for k, v in tree.items():
            out.update(flatten(v, f'{prefix}/{k}' if prefix else str(k)))
        return out
    return {prefix: tree}


def init_weights(cfg: dict, seed: int):
    """Random weights in the parameter dtype, made on the device by one
    jitted call: N(0, 0.02) embedding, N(0, 1/fan_in) matrices, zero
    biases, unit norm scales."""
    layout = param_layout(cfg)
    dtype = jnp.dtype(cfg['param_dtype'])

    def make(key):
        flat = {}
        for i, path in enumerate(sorted(layout)):
            shape, init = layout[path]
            k = jax.random.fold_in(key, i)
            if init == 'zeros':
                flat[path] = jnp.zeros(shape, dtype)
            elif init == 'ones':
                flat[path] = jnp.ones(shape, dtype)
            else:
                std = 0.02 if init == 'embed' else 1.0 / math.sqrt(shape[-2])
                flat[path] = (jax.random.normal(k, shape, jnp.float32)
                              * std).astype(dtype)
        return _unflatten(flat)

    return jax.jit(make)(seed_key(seed))


def flops_per_token(cfg: dict, seq_len: int) -> float:
    """Model FLOPs of one trained token: 6 x the matmul parameters of the
    layers and the head (a tied head counts the embedding once; the lookup
    counts nothing) plus causal attention at 6 x layers x heads x head_dim x
    sequence.  Nothing for recomputation, nothing for the optimizer."""
    layout = param_layout(cfg)
    mm = sum(math.prod(s) for p, (s, _) in layout.items()
             if p.startswith('blocks/') and p.endswith('/w'))
    head = cfg['d_model'] * cfg['vocab']
    attn = cfg['n_layers'] * cfg['n_heads'] * cfg['head_dim'] * seq_len
    return 6.0 * (mm + head) + 6.0 * attn


# ---------------------------------------------------------------------------
# precision


def _round_to(y, mbits: int, emin: int):
    """Round to a float format with ``mbits`` mantissa bits whose smallest
    normal exponent is ``emin`` (subnormals below it); no range clip."""
    e = jnp.floor(jnp.log2(jnp.maximum(jnp.abs(y), 2.0 ** emin)))
    ulp = jnp.exp2(e - mbits)
    return jnp.round(y / ulp) * ulp


def _quant(x, mbits: int, emin: int, top: float):
    """Per-tensor scaled fake quantization (amax maps to the format's top)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return jnp.clip(_round_to(x / scale, mbits, emin), -top, top) * scale


_e4m3 = functools.partial(_quant, mbits=3, emin=-6, top=448.0)
_e5m2 = functools.partial(_quant, mbits=2, emin=-14, top=57344.0)


@jax.custom_vjp
def _fwd_e4m3(x):
    return _e4m3(x)


_fwd_e4m3.defvjp(lambda x: (_e4m3(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _bwd_e5m2(y):
    return y


_bwd_e5m2.defvjp(lambda y: (y, None), lambda _, g: (_e5m2(g),))


def make_einsum(precision: str):
    if precision == 'f32':
        return jnp.einsum
    if precision == 'fp8':
        def einsum(spec, a, b):
            return _bwd_e5m2(jnp.einsum(spec, _fwd_e4m3(a), _fwd_e4m3(b)))
        return einsum
    raise ValueError(f'unknown precision {precision!r}')


# ---------------------------------------------------------------------------
# model pieces (all f32)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (S, heads, dh): rotate-half RoPE at positions 0..S-1."""
    s, dh = x.shape[0], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(cfg, ein, p, taps, x):
    """One block over x (B, S, D).  Returns (y, a-stats): the token mean of
    each preconditioned linear's input."""
    H, KV, dh = cfg['n_heads'], cfg['n_kv_heads'], cfg['head_dim']
    eps, theta = cfg['norm_eps'], cfg['rope_theta']
    B, S, _ = x.shape
    a = {}

    def lin(name, inp):
        a[name] = jnp.mean(inp.reshape(-1, inp.shape[-1]), 0)
        y = ein('bsi,io->bso', inp, p[f'{name}/w'])
        if f'{name}/b' in p:
            y = y + p[f'{name}/b']
        return y + taps[name]

    h = _rmsnorm(x, p['norm1/scale'], eps)
    q = lin('attn/q', h).reshape(B, S, H, dh)
    k = lin('attn/k', h).reshape(B, S, KV, dh)
    v = lin('attn/v', h).reshape(B, S, KV, dh)
    g = H // KV

    @jax.checkpoint
    def attend(args):
        qr, kr, vr = args                       # one batch row
        qr = _rope(qr, theta).reshape(S, KV, g, dh)
        kr = _rope(kr, theta)
        s = ein('qkgd,skd->kgqs', qr, kr) / math.sqrt(dh)
        causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(causal, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return ein('kgqs,skd->qkgd', w, vr).reshape(S, H * dh)

    att = jax.lax.map(attend, (q, k, v))
    x = x + lin('attn/o', att)
    h2 = _rmsnorm(x, p['norm2/scale'], eps)
    mid = jax.nn.silu(lin('mlp/gate', h2)) * lin('mlp/up', h2)
    return x + lin('mlp/down', mid), a


def _head_blocks(cfg, ein, w_head, scale, x, labels, n_total):
    """Sum over token blocks of the cross-entropy / n_total, and its vjp
    pieces.  x (N, D), labels (N,).  Returns (loss, dx, dscale, dW, ā, b̄)
    with dW in w_head's (D, V) layout."""
    N, D = x.shape
    blk = math.gcd(N, HEAD_TOKENS)
    xb = x.reshape(N // blk, blk, D)
    lb = labels.reshape(N // blk, blk)

    def block_loss(w, sc, xc, lc, tap):
        h = _rmsnorm(xc, sc, cfg['norm_eps'])
        logits = ein('ti,io->to', h, w) + tap
        lse = jax.scipy.special.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, lc[:, None], -1)[:, 0]
        return jnp.sum(lse - gold) / n_total, jnp.sum(h, 0)

    def body(carry, xs):
        loss, dsc, dw, hsum, tsum = carry
        xc, lc = xs
        tap = jnp.zeros((w_head.shape[1],), jnp.float32)
        l, vjp, hs = jax.vjp(block_loss, w_head, scale, xc, lc, tap,
                             has_aux=True)
        gw, gsc, gx, _, gtap = vjp(jnp.ones((), jnp.float32))
        return (loss + l, dsc + gsc, dw + gw, hsum + hs, tsum + gtap), gx

    init = (jnp.zeros((), jnp.float32), jnp.zeros_like(scale),
            jnp.zeros_like(w_head), jnp.zeros((D,), jnp.float32),
            jnp.zeros((w_head.shape[1],), jnp.float32))
    (loss, dsc, dw, hsum, tsum), gx = jax.lax.scan(body, init, (xb, lb))
    return loss, gx.reshape(N, D), dsc, dw, hsum / n_total, tsum


# ---------------------------------------------------------------------------
# the reference trainer


class Reference:
    """Training steps of the model in ``cfg``, layer by layer in f32, under
    ``optimizer`` (a ``bench/optimizers/<name>.py`` reference: per-leaf
    ``init``, ``fold`` of a gradient with the layer's statistics, ``factor``
    from the summed partials, ``apply``, and ``first_update``).

    ``run`` returns the readings that the benchmark compares: each step's
    loss, the per-leaf norm of the first gradient, of the optimizer's first
    update (``first_update`` after step 1) and of the parameters' change
    after the last step."""

    def __init__(self, cfg: dict, optimizer, precision: str = 'f32'):
        self.cfg, self.opt = cfg, optimizer
        self.ein = make_einsum(precision)
        self._build()

    def _build(self):
        cfg, ein = self.cfg, self.ein
        f32 = lambda t: jax.tree_util.tree_map(
            lambda z: z.astype(jnp.float32), t)

        def zero_taps(p):
            return {n: jnp.zeros((p[f'{n}/w'].shape[-1],), jnp.float32)
                    for n in PRECON}

        @jax.jit
        def fwd(p, x):
            with jax.default_matmul_precision('highest'):
                p = f32(p)
                return _layer(cfg, ein, p, zero_taps(p), x)

        @jax.jit
        def bwd(p, x, dy):
            with jax.default_matmul_precision('highest'):
                p = f32(p)
                f = lambda pp, tt, xx: _layer(cfg, ein, pp, tt, xx)[0]
                _, vjp = jax.vjp(f, p, zero_taps(p), x)
                dp, dtaps, dx = vjp(dy)
                return dx, dp, dtaps

        @jax.jit
        def head(w_head, scale, x, labels):
            with jax.default_matmul_precision('highest'):
                B, S, D = x.shape
                loss, dx, dsc, dw, a, b = _head_blocks(
                    cfg, ein, w_head.astype(jnp.float32),
                    scale.astype(jnp.float32), x.reshape(B * S, D),
                    labels.reshape(-1), float(B * S))
                return loss, dx.reshape(B, S, D), dsc, dw, a, b

        @jax.jit
        def embed(table, tokens):
            return table.astype(jnp.float32)[tokens]

        @jax.jit
        def embed_grad(table, tokens, dx):
            return jnp.zeros(table.shape, jnp.float32).at[tokens].add(dx)

        self._fwd, self._bwd, self._head = fwd, bwd, head
        self._embed, self._embed_grad = embed, embed_grad
        self._sq = jax.jit(lambda x: jnp.sum(jnp.square(
            x.astype(jnp.float32))))

    def run(self, params, batches) -> dict:
        """``params``: nested weights in the program's layout (consumed);
        ``batches``: per step, ``(tokens, labels)`` int arrays (B, S)."""
        cfg, opt = self.cfg, self.opt
        flat = flatten(params)
        del params
        L = cfg['n_layers']
        layer_names = sorted({p[len('blocks/'):] for p in flat
                              if p.startswith('blocks/')})
        # per-layer slices: the reference walks layer by layer
        layers = [{n: flat[f'blocks/{n}'][l] for n in layer_names}
                  for l in range(L)]
        other = {p: v for p, v in flat.items() if not p.startswith('blocks/')}
        del flat
        theta0 = {'layers': [dict(d) for d in layers], 'other': dict(other)}
        # the preconditioned linears (the layers' and an untied head) get
        # their statistics: the token mean of the input, the summed cotangent
        tapped = lambda n: n.endswith('/w') and n[:-2] in PRECON
        state = {'layers': [{n: opt.init(v.shape, tapped(n))
                             for n, v in d.items()} for d in layers],
                 'other': {p: opt.init(v.shape, p == 'lm_head/w')
                           for p, v in other.items()}}
        tied = cfg['tie_embeddings']
        losses, grad1, update1 = [], None, None

        for step, (tokens, labels) in enumerate(batches):
            count = float(step + 1)
            tokens = jnp.asarray(tokens)
            labels = jnp.asarray(labels)
            xs = [self._embed(other['embed/table'], tokens)]
            stats_a = []
            for l in range(L):
                y, a = self._fwd(layers[l], xs[-1])
                xs.append(y)
                stats_a.append(a)
            w_head = other['embed/table'].T if tied else other['lm_head/w']
            loss, dx, dsc, dw, a_h, b_h = self._head(
                w_head, other['norm_f/scale'], xs.pop(), labels)
            losses.append(float(loss))
            partial = jnp.zeros((), jnp.float32)
            gsq: dict = {}

            def fold(slot, key, path, g, stats=None):
                nonlocal partial
                slot[key], part = opt.fold(slot[key], g, stats, count)
                partial = partial + part
                gsq[path] = gsq.get(path, 0.0) + self._sq(g)

            if tied:
                emb_from_head = dw.T
            else:
                fold(state['other'], 'lm_head/w', 'lm_head/w', dw, (a_h, b_h))
            fold(state['other'], 'norm_f/scale', 'norm_f/scale', dsc)
            del dw
            for l in reversed(range(L)):
                dx, dp, b = self._bwd(layers[l], xs.pop(), dx)
                for n in layer_names:
                    stats = (stats_a[l][n[:-2]], b[n[:-2]]) if tapped(n) \
                        else None
                    fold(state['layers'][l], n, f'blocks/{n}', dp[n], stats)
                del dp
            g_emb = self._embed_grad(other['embed/table'], tokens, dx)
            if tied:
                g_emb = g_emb + emb_from_head
            fold(state['other'], 'embed/table', 'embed/table', g_emb)
            del g_emb, dx
            factor = opt.factor(partial)
            for l in range(L):
                for n in layer_names:
                    layers[l][n], state['layers'][l][n] = opt.apply(
                        layers[l][n], state['layers'][l][n], factor, count)
            for p in list(other):
                other[p], state['other'][p] = opt.apply(
                    other[p], state['other'][p], factor, count)
            if step == 0:
                grad1 = {p: math.sqrt(float(v)) for p, v in gsq.items()}
                update1 = self._norms(state)
        del state
        diff_sq = jax.jit(lambda a, b: jnp.sum(jnp.square(
            a.astype(jnp.float32) - b.astype(jnp.float32))))
        change = {p: math.sqrt(float(diff_sq(other[p], theta0['other'][p])))
                  for p in other}
        for n in layer_names:
            change[f'blocks/{n}'] = math.sqrt(sum(
                float(diff_sq(layers[l][n], theta0['layers'][l][n]))
                for l in range(L)))
        return {'losses': losses, 'grad1': grad1, 'update1': update1,
                'change': change}

    def _norms(self, state) -> dict:
        """Per leaf, the norm of the optimizer's first update."""
        up = self.opt.first_update
        out = {p: math.sqrt(float(self._sq(up(v))))
               for p, v in state['other'].items()}
        sq: dict = {}
        for d in state['layers']:
            for n, v in d.items():
                sq[f'blocks/{n}'] = sq.get(f'blocks/{n}', 0.0) + \
                    float(self._sq(up(v)))
        out.update({p: math.sqrt(v) for p, v in sq.items()})
        return out


def batches_for(stream, steps: int) -> list:
    """The first ``steps`` batches of a ``bench.traffic.TokenStream``."""
    out = []
    for s in range(steps):
        t = stream.tokens_at(s)
        out.append((np.asarray(t[:, :-1]), np.asarray(t[:, 1:])))
    return out
