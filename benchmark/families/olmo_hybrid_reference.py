"""Plain reference for the `olmo_hybrid` family: causal-LM training of one
chip's share of the Olmo-Hybrid decoder on packed rows, written from the
published configuration, the Gated DeltaNet paper (arXiv:2412.06464) and the
OLMo 2 block (arXiv:2501.00656), in `jax.numpy` and float32. It imports
nothing of the program (`harness.rounding` is the benchmark's own).

The net: token embedding; blocks `h = x + RMSNorm(mixer(x)); out = h +
RMSNorm(SwiGLU(h))` (the norm BEHIND each sublayer); a final RMSNorm; an
untied head; the mean cross-entropy of the next token over the positions
whose next token lies in the same document.

- Gated DeltaNet (`layer_types[i] == 'linear_attention'`), per held head,
  keys of `linear_key_head_dim` (96), values of `linear_value_head_dim` (192):
  q = l2norm(silu(conv(x W_q))), k likewise, v = silu(conv(x W_v)) with a
  depthwise causal convolution of `linear_conv_kernel_dim` taps;
  g_t = -exp(A_log) softplus(x W_a + dt_bias), ONE scalar a head;
  beta_t = sigmoid(x W_b), doubled where `linear_allow_neg_eigval`;
  S_t = exp(g_t) S_{t-1}; S_t += beta_t k_t (v_t - S_t^T k_t)^T;
  o_t = S_t^T q_t / sqrt(d_k); out = [RMSNorm_head(o_t) silu(x W_g)] W_o.
  The recurrence is computed as it stands, ONE TOKEN AT A TIME (a scan over
  the row; only its memory is chunked: the states inside a stretch of 64
  tokens are recomputed in the backward pass). State and convolution start
  anew at a document boundary.
- Full attention: q = RMSNorm(x W_q), k = RMSNorm(x W_k), each norm over the
  whole projection this share holds, v = x W_v; softmax of q k^T / sqrt(128)
  over the keys of the same document up to the query, no rotation; W_o. The
  whole score matrix of a head, one head at a time.
- SwiGLU of `intermediate_size`, whole, in every layer.

The share: the projections are the held heads' columns and W_o their rows,
so a mixer's output is this share's addend of the layer's result, and that
addend is what is normed and added. What the other chip of the pair would
add is left out, as the program leaves it out.

Departures from the published description, each stated by the
configuration's `assumed`: the norm's place and the q/k norms (config.json
has no key for either), no rotary, l2norm adds 1e-6 under the root, AdamW at
a constant rate on every parameter, weights from the seed.

`precision`:
- 'float32'  the reference: every product at `highest`.
- 'float8'   the CONTROL: the operands of every matrix product (the
             projections, the scores and the weighted values, the state's
             reads and rank-one writes, the head) rounded to e4m3 with a
             per-tensor scale. `correct` has to come out false for it.

Memory: a step is taken block by block. The forward pass keeps each block's
input; the backward pass walks the blocks from the head down, takes one
block's gradient with `jax.vjp`, hands it to AdamW and lets it go, so the
device never holds a whole gradient beside the parameters and the moments.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from harness.rounding import round_to

HIGH = jax.lax.Precision.HIGHEST
STRETCH = 64        # tokens of the recurrence recomputed together


def _mm(a, b, precision):
    return jnp.matmul(round_to(a, precision), round_to(b, precision),
                      precision=HIGH)


def _einsum(spec, a, b, precision):
    return jnp.einsum(spec, round_to(a, precision), round_to(b, precision),
                      precision=HIGH)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _conv(x, w, seg):
    """y_t = sum_i w[i] x_{t-n+1+i} over the taps inside t's document."""
    n = w.shape[0]
    T = x.shape[1]
    y = jnp.zeros_like(x)
    for i in range(n):
        back = n - 1 - i
        shifted = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :T]
        there = jnp.pad(seg, ((0, 0), (back, 0)), constant_values=-1)[:, :T]
        y = y + jnp.where((there == seg)[..., None], shifted, 0.0) * w[i]
    return y


def _delta_rule(q, k, v, g, beta, first, precision):
    """The recurrence, one token at a time. q, k (B, T, H, K); v (B, T, H,
    V); g, beta (B, T, H); first (B, T): the token starts a document."""
    B, T, H, K = q.shape
    stretch = STRETCH if T % STRETCH == 0 else T

    def token(S, xs):
        q_t, k_t, v_t, g_t, b_t, new = xs
        S = jnp.where(new[:, None, None, None], 0.0, S)
        S = S * jnp.exp(g_t)[..., None, None]
        read = _einsum('bhkv,bhk->bhv', S, k_t, precision)
        S = S + _einsum('bhk,bhv->bhkv', k_t, b_t[..., None] * (v_t - read),
                        precision)
        return S, _einsum('bhkv,bhk->bhv', S, q_t, precision)

    @jax.checkpoint
    def some(S, xs):
        return jax.lax.scan(token, S, xs)

    def cut(x):         # (B, T, ...) -> (T / stretch, stretch, B, ...)
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape((T // stretch, stretch) + x.shape[1:])

    _, o = jax.lax.scan(some, jnp.zeros((B, H, K, v.shape[-1]), jnp.float32),
                        tuple(cut(x) for x in (q, k, v, g, beta, first)))
    return jnp.moveaxis(o.reshape((T,) + o.shape[2:]), 0, 1) / np.sqrt(K)


def _gated_delta_net(cfg, w, x, seg, precision):
    B, T, _ = x.shape
    H = cfg['heads_held'][1]
    K, V = cfg['linear_key_head_dim'], cfg['linear_value_head_dim']
    first = jnp.concatenate([jnp.ones((B, 1), bool),
                             seg[:, 1:] != seg[:, :-1]], axis=1)

    def short(proj, conv, d):
        y = jax.nn.silu(_conv(_mm(x, w[proj], precision), w[conv], seg))
        return y.reshape(B, T, H, d)

    def l2norm(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True)
                                 + cfg['assumed_values']['l2norm_eps'])

    q = l2norm(short('q_proj', 'q_conv', K))
    k = l2norm(short('k_proj', 'k_conv', K))
    v = short('v_proj', 'v_conv', V)
    g = -jnp.exp(w['A_log']) * jax.nn.softplus(
        _mm(x, w['a_proj'], precision) + w['dt_bias'])
    beta = jax.nn.sigmoid(_mm(x, w['b_proj'], precision)) \
        * (2.0 if cfg['linear_allow_neg_eigval'] else 1.0)
    gate = jax.nn.silu(_mm(x, w['g_proj'], precision))
    o = _delta_rule(q, k, v, g, beta, first, precision)
    o = _rms_norm(o, w['o_norm'], cfg['rms_norm_eps'])
    return _mm(o.reshape(B, T, H * V) * gate, w['o_proj'], precision)


def _attention(cfg, w, x, seg, precision):
    B, T, _ = x.shape
    H, D = cfg['heads_held'][1], cfg['assumed_values']['head_dim']
    eps = cfg['rms_norm_eps']
    q = _rms_norm(_mm(x, w['q_proj'], precision), w['q_norm'], eps)
    k = _rms_norm(_mm(x, w['k_proj'], precision), w['k_norm'], eps)
    v = _mm(x, w['v_proj'], precision)
    t = jnp.arange(T)
    sees = (seg[:, :, None] == seg[:, None, :]) \
        & (t[:, None] >= t[None, :])[None]                  # (B, T, T)

    @jax.checkpoint
    def head(xs):
        q, k, v = xs                                        # (B, T, D)
        s = _einsum('bqd,bkd->bqk', q, k, precision) / np.sqrt(D)
        p = jax.nn.softmax(jnp.where(sees, s, -1e30), axis=-1)
        return _einsum('bqk,bkd->bqd', p, v, precision)

    def heads(x):       # (B, T, H * D) -> (H, B, T, D)
        return jnp.moveaxis(x.reshape(B, T, H, D), 2, 0)

    o = jax.lax.map(head, (heads(q), heads(k), heads(v)))
    return _mm(jnp.moveaxis(o, 0, 2).reshape(B, T, H * D), w['o_proj'],
               precision)


def _swiglu(x, gate, up, down, precision):
    return _mm(jax.nn.silu(_mm(x, gate, precision)) * _mm(x, up, precision),
               down, precision)


def mix(cfg, kind, precision, w, x, seg):
    """x + RMSNorm(mixer(x)): the first half of a block, a row at a time (a
    row's temporaries are let go before the next row's are made)."""
    mixer = {k[6:]: v for k, v in w.items() if k.startswith('mixer.')}
    layer = _gated_delta_net if kind == 'linear_attention' else _attention

    @jax.checkpoint
    def row(xs):
        x, seg = xs[0][None], xs[1][None]
        y = layer(cfg, mixer, x, seg, precision)
        return (x + _rms_norm(y, w['post_attention_norm.weight'],
                              cfg['rms_norm_eps']))[0]

    return jax.lax.map(row, (x, seg))


def block(cfg, kind, precision, w, x, seg):
    """One decoder block; `w` holds its leaves without the `layers.i.`."""
    h = mix(cfg, kind, precision, w, x, seg)
    y = _swiglu(h, w['mlp.gate_proj'], w['mlp.up_proj'], w['mlp.down_proj'],
                precision)
    return h + _rms_norm(y, w['post_feedforward_norm.weight'],
                         cfg['rms_norm_eps'])


def head_loss(cfg, precision, w, x, labels):
    """Sum over the batch of the next-token cross-entropies over `count`,
    the number of positions that have a label."""
    count = jnp.maximum(jnp.sum(labels >= 0), 1).astype(jnp.float32)

    @jax.checkpoint
    def row(xs):
        x, labels = xs
        logits = _mm(_rms_norm(x, w['norm.weight'], cfg['rms_norm_eps']),
                     w['lm_head'], precision)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, jnp.maximum(labels, 0)[:, None],
                                   axis=-1)[:, 0]
        return jnp.sum(jnp.where(labels >= 0, nll, 0.0))

    return jnp.sum(jax.lax.map(row, (x, labels))) / count


def layer_leaves(params, i):
    prefix = 'layers.%d.' % i
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def adamw_update(params, grads, moments, t, *, lr, beta1, beta2, eps,
                 weight_decay):
    """Algorithm 2 of arXiv:1711.05101, one step (t counts from 1)."""
    new_p, new_m = {}, {}
    for k, p in params.items():
        g = grads[k]
        m = beta1 * moments[k][0] + (1 - beta1) * g
        v = beta2 * moments[k][1] + (1 - beta2) * g * g
        m_hat = m / (1 - beta1 ** t)
        v_hat = v / (1 - beta2 ** t)
        new_p[k] = p - lr * (m_hat / (jnp.sqrt(v_hat) + eps)
                             + weight_decay * p)
        new_m[k] = (m, v)
    return new_p, new_m


def follow_steps(cfg, optim, params, batches, precision='float32'):
    """Follow the first len(batches) optimizer steps from `params`.

    `batches` are the host batches the program was fed, each
    ((ids, segment_ids, labels), ()). Returns {'losses': [...],
    'first_gradient': {leaf: array, on the host}, 'change_norms': {leaf:
    norm of (params after the steps - params)}}.
    """
    layers = cfg['num_hidden_layers']
    kinds = cfg['layer_types'][:layers]
    start = jax.device_get(params)          # on the host until the end
    p = dict(params)
    del params
    moments = {k: (jnp.zeros_like(v), jnp.zeros_like(v))
               for k, v in p.items()}

    @functools.partial(jax.jit, static_argnums=0)
    def forward(kind, w, x, seg):
        return block(cfg, kind, precision, w, x, seg)

    @functools.partial(jax.jit, static_argnums=0)
    def backward(kind, w, x, seg, gx):
        _, vjp = jax.vjp(lambda w, x: block(cfg, kind, precision, w, x, seg),
                         w, x)
        return vjp(gx)

    @jax.jit
    def head(w, x, labels):
        loss, (gw, gx) = jax.value_and_grad(
            functools.partial(head_loss, cfg, precision), argnums=(0, 1))(
                w, x, labels)
        return loss, gw, gx

    @jax.jit
    def embedding_gradient(table, ids, gx):
        return jnp.zeros_like(table).at[ids].add(gx)

    @functools.partial(jax.jit, donate_argnums=(0, 2))
    def update(p, g, m, t):
        return adamw_update(p, g, m, t, lr=optim['learning_rate'],
                            beta1=optim['beta1'], beta2=optim['beta2'],
                            eps=optim['epsilon'],
                            weight_decay=optim['weight_decay'])

    out = {'losses': [], 'first_gradient': {}}

    def apply(names, grads, t):
        """AdamW on the leaves `names` (whole names), their gradients given
        under the same names; the first step's gradients go to the host."""
        if t == 1:
            out['first_gradient'].update(jax.device_get(grads))
        new_p, new_m = update({k: p[k] for k in names}, grads,
                              {k: moments[k] for k in names}, jnp.float32(t))
        p.update(new_p)
        moments.update(new_m)

    for t, ((ids, seg, labels), _) in enumerate(batches, 1):
        ids, seg, labels = (jnp.asarray(v) for v in (ids, seg, labels))
        xs = [p['embed_tokens.weight'][ids]]
        for i in range(layers):
            xs.append(forward(kinds[i], layer_leaves(p, i), xs[-1], seg))
        top = {k: p[k] for k in ('norm.weight', 'lm_head')}
        loss, gw, gx = head(top, xs.pop(), labels)
        out['losses'].append(float(loss))
        apply(list(top), gw, t)
        del top, gw
        for i in reversed(range(layers)):
            prefix = 'layers.%d.' % i
            gw, gx = backward(kinds[i], layer_leaves(p, i), xs.pop(), seg, gx)
            gw = {prefix + k: v for k, v in gw.items()}
            apply(list(gw), gw, t)
            del gw
        g = {'embed_tokens.weight': embedding_gradient(
            p['embed_tokens.weight'], ids, gx)}
        apply(list(g), g, t)
        del g, gx
    norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
    out['change_norms'] = {k: float(norm(p[k], jnp.asarray(start[k])))
                           for k in start}
    return out
