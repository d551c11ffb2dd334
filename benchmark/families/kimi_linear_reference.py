"""Plain reference for the `kimi_linear` family: causal-LM training of the
Kimi-Linear hybrid decoder on packed rows, written from the paper
(arXiv:2510.26692) and the published configuration, in `jax.numpy` and
float32. It imports nothing of the program (`harness.rounding` is the
benchmark's own).

The net: token embedding; blocks `x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x))`;
a final RMSNorm; an untied head; the mean cross-entropy of the next token
over the positions whose next token lies in the same document.

- KDA (layers in `kda_layers`), per head, d_k = d_v = head_dim:
  q = l2norm(silu(conv(x W_q))), k likewise, v = silu(conv(x W_v)) with a
  depthwise causal convolution of `short_conv_kernel_size` taps;
  g_t = -exp(A_log) softplus(x W_a1 W_a2 + dt_bias); beta_t = sigmoid(x W_b);
  S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T;
  o_t = S_t^T q_t / sqrt(d_k); out = W_o [RMSNorm_head(o_t) sigmoid(x W_g1 W_g2)].
  The recurrence is computed as it stands, ONE TOKEN AT A TIME (a scan over
  the row; only its memory is chunked: the states inside a stretch of 64
  tokens are recomputed in the backward pass). State and convolution start
  anew at a document boundary.
- MLA without position encoding (layers in `full_attn_layers`): q = x W_q
  (heads x (nope + rope)); [c, k_pe] = x W_kva, c = RMSNorm(c);
  [k_nope, v] = c W_kvb; k = [k_nope, k_pe shared]; softmax of
  q k^T / sqrt(nope + rope) over the keys of the same document up to the
  query; W_o. The whole score matrix of a head, one head at a time.
- FFN: SwiGLU of `intermediate_size` in the first `first_k_dense_replace`
  layers; after them the expert layer: s = sigmoid(x W_r) over all
  `num_experts_total` experts, the top `num_experts_per_token` by
  s + e_score_correction_bias (zero here), weights s_sel / sum(s_sel) *
  `routed_scaling_factor`, and THIS CHIP'S SHARE of the routed sum, the
  experts `experts_held` (a loop over the held experts, each over every
  token, masked), plus the shared expert. The routing is the reference's
  own: it is not handed the program's.

Departures from the paper and the released code, each stated by the
configuration's `assumed`: the two low-rank pairs have rank head_dim and no
bias; l2norm adds 1e-6 under the root; AdamW at a constant rate on every
parameter; weights from the seed.

`precision`:
- 'float32'  the reference: every product at `highest`.
- 'float8'   the CONTROL: the operands of every matrix product (the
             projections, the scores and the weighted values, the state's
             reads and rank-one writes, the experts, the head) rounded to
             e4m3 with a per-tensor scale; the router stays in float32, as
             it does in the program. `correct` has to come out false for it.

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
HEAD_GROUP = 1      # heads of full attention whose scores are held at once


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
    """The recurrence, one token at a time. (B, T, H, d) each; beta
    (B, T, H); first (B, T): the token starts a document."""
    B, T, H, d = q.shape
    stretch = STRETCH if T % STRETCH == 0 else T

    def token(S, xs):
        q_t, k_t, v_t, g_t, b_t, new = xs
        S = jnp.where(new[:, None, None, None], 0.0, S)
        S = S * jnp.exp(g_t)[..., None]
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

    _, o = jax.lax.scan(some, jnp.zeros((B, H, d, v.shape[-1]), jnp.float32),
                        tuple(cut(x) for x in (q, k, v, g, beta, first)))
    return jnp.moveaxis(o.reshape((T,) + o.shape[2:]), 0, 1) / np.sqrt(d)


def _kda(cfg, w, x, seg, precision):
    B, T, _ = x.shape
    H = cfg['linear_attn_config']['num_heads']
    d = cfg['linear_attn_config']['head_dim']
    first = jnp.concatenate([jnp.ones((B, 1), bool),
                             seg[:, 1:] != seg[:, :-1]], axis=1)

    def short(proj, conv):
        y = jax.nn.silu(_conv(_mm(x, w[proj], precision), w[conv], seg))
        return y.reshape(B, T, H, d)

    def l2norm(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

    q, k = l2norm(short('q_proj', 'q_conv')), l2norm(short('k_proj', 'k_conv'))
    v = short('v_proj', 'v_conv')
    raw = _mm(_mm(x, w['decay_a'], precision), w['decay_b'], precision) \
        + w['dt_bias']
    g = -jnp.exp(w['A_log'])[:, None] * jax.nn.softplus(raw).reshape(B, T, H, d)
    beta = jax.nn.sigmoid(_mm(x, w['beta_proj'], precision))
    gate = jax.nn.sigmoid(_mm(_mm(x, w['gate_a'], precision), w['gate_b'],
                              precision))
    o = _delta_rule(q, k, v, g, beta, first, precision)
    o = _rms_norm(o, w['o_norm'], cfg['rms_norm_eps'])
    return _mm(o.reshape(B, T, H * d) * gate, w['o_proj'], precision)


def _mla(cfg, w, x, seg, precision):
    B, T, _ = x.shape
    H = cfg['num_attention_heads']
    nope, rope = cfg['qk_nope_head_dim'], cfg['qk_rope_head_dim']
    dv, rank = cfg['v_head_dim'], cfg['kv_lora_rank']
    q = _mm(x, w['q_proj'], precision).reshape(B, T, H, nope + rope)
    kva = _mm(x, w['kv_a_proj'], precision)
    c = _rms_norm(kva[..., :rank], w['kv_a_norm'], cfg['rms_norm_eps'])
    kv = _mm(c, w['kv_b_proj'], precision).reshape(B, T, H, nope + dv)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        kva[..., None, rank:], (B, T, H, rope))], axis=-1)
    v = kv[..., nope:]
    t = jnp.arange(T)
    sees = (seg[:, :, None] == seg[:, None, :]) \
        & (t[:, None] >= t[None, :])[None]                  # (B, T, T)
    group = HEAD_GROUP if H % HEAD_GROUP == 0 else 1

    @jax.checkpoint
    def heads(xs):
        q, k, v = xs                                        # (B, T, group, d)
        s = _einsum('bqhd,bkhd->bhqk', q, k, precision) / np.sqrt(nope + rope)
        p = jax.nn.softmax(jnp.where(sees[:, None], s, -1e30), axis=-1)
        return _einsum('bhqk,bkhd->bqhd', p, v, precision)

    def grouped(x):     # (B, T, H, d) -> (H / group, B, T, group, d)
        return jnp.moveaxis(x.reshape(B, T, H // group, group, -1), 2, 0)

    o = jax.lax.map(heads, (grouped(q), grouped(k), grouped(v)))
    o = jnp.moveaxis(o, 0, 2).reshape(B, T, H * dv)
    return _mm(o, w['o_proj'], precision)


def _swiglu(x, gate, up, down, precision):
    return _mm(jax.nn.silu(_mm(x, gate, precision)) * _mm(x, up, precision),
               down, precision)


def route(cfg, w, x):
    """-> (idx (..., k), weights (..., k)): float32 whatever `precision`."""
    s = jax.nn.sigmoid(jnp.matmul(x, w['mlp.router'], precision=HIGH))
    _, idx = jax.lax.top_k(s + w['mlp.e_score_correction_bias'],
                           cfg['num_experts_per_token'])
    picked = jnp.take_along_axis(s, idx, axis=-1)
    return idx, picked / jnp.sum(picked, -1, keepdims=True) \
        * cfg['routed_scaling_factor']


def _moe(cfg, w, x, precision):
    lo, hi = cfg['experts_held']
    idx, weights = route(cfg, w, x)
    expert = jax.checkpoint(functools.partial(_swiglu, precision=precision))
    y = expert(x, w['mlp.shared.gate_proj'], w['mlp.shared.up_proj'],
               w['mlp.shared.down_proj'])
    for e in range(lo, hi):
        share = jnp.sum(jnp.where(idx == e, weights, 0.0), axis=-1)
        y = y + share[..., None] * expert(
            x, w['mlp.experts_gate'][e - lo], w['mlp.experts_up'][e - lo],
            w['mlp.experts_down'][e - lo])
    return y


def kinds(cfg, i):
    """(attention, feed-forward) of layer i, 0-based in the leaf names."""
    return ('kda' if i + 1 in cfg['linear_attn_config']['kda_layers']
            else 'mla',
            'dense' if i < cfg['first_k_dense_replace'] else 'moe')


def attend(cfg, kind, precision, w, x, seg):
    """x + Attn(RMSNorm(x)): the first half of a block, a row at a time (a
    row's temporaries are let go before the next row's are made)."""
    attention = {k[10:]: v for k, v in w.items() if k.startswith('attention.')}
    layer = _kda if kind[0] == 'kda' else _mla

    @jax.checkpoint
    def row(xs):
        x, seg = xs[0][None], xs[1][None]
        h = _rms_norm(x, w['input_norm.weight'], cfg['rms_norm_eps'])
        return (x + layer(cfg, attention, h, seg, precision))[0]

    return jax.lax.map(row, (x, seg))


def block(cfg, kind, precision, w, x, seg):
    """One decoder block; `w` holds its leaves without the `layers.i.`."""
    x = attend(cfg, kind, precision, w, x, seg)
    h = _rms_norm(x, w['post_attention_norm.weight'], cfg['rms_norm_eps'])
    if kind[1] == 'dense':
        return x + _swiglu(h, w['mlp.gate_proj'], w['mlp.up_proj'],
                           w['mlp.down_proj'], precision)
    return x + _moe(cfg, w, h, precision)


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


def with_buffers(cfg, w):
    """A block's leaves with the router's correction bias (a buffer of the
    program, zero as the configuration states) beside them."""
    if 'mlp.router' in w:
        w = dict(w, **{'mlp.e_score_correction_bias': jnp.zeros(
            (cfg['num_experts_total'],), jnp.float32)})
    return w


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


def follow_steps(cfg, optim, params, batches, precision='float32',
                 routing=None):
    """Follow the first len(batches) optimizer steps from `params`.

    `batches` are the host batches the program was fed, each
    ((ids, segment_ids, labels), ()). Returns {'losses': [...],
    'first_gradient': {leaf: array, on the host}, 'change_norms': {leaf:
    norm of (params after the steps - params)}}. `routing`, a list, is
    given the first step's selected experts, one (B, T, k) array per expert
    layer, sorted along k.
    """
    layers = cfg['num_hidden_layers']
    start = jax.device_get(params)          # on the host until the end
    p = dict(params)
    del params
    moments = {k: (jnp.zeros_like(v), jnp.zeros_like(v))
               for k, v in p.items()}

    @functools.partial(jax.jit, static_argnums=0)
    def forward(kind, w, x, seg):
        return block(cfg, kind, precision, with_buffers(cfg, w), x, seg)

    @functools.partial(jax.jit, static_argnums=0)
    def backward(kind, w, x, seg, gx):
        _, vjp = jax.vjp(lambda w, x: block(
            cfg, kind, precision, with_buffers(cfg, w), x, seg), w, x)
        return vjp(gx)

    @functools.partial(jax.jit, static_argnums=0)
    def selected(kind, w, x, seg):
        w = with_buffers(cfg, w)
        x = attend(cfg, kind, precision, w, x, seg)
        h = _rms_norm(x, w['post_attention_norm.weight'], cfg['rms_norm_eps'])
        return jnp.sort(route(cfg, w, h)[0], axis=-1)

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
            w = layer_leaves(p, i)
            if t == 1 and routing is not None and kinds(cfg, i)[1] == 'moe':
                routing.append(np.asarray(selected(kinds(cfg, i), w, xs[-1],
                                                   seg)))
            xs.append(forward(kinds(cfg, i), w, xs[-1], seg))
        top = {k: p[k] for k in ('norm.weight', 'lm_head')}
        loss, gw, gx = head(top, xs.pop(), labels)
        out['losses'].append(float(loss))
        apply(list(top), gw, t)
        del top, gw
        for i in reversed(range(layers)):
            prefix = 'layers.%d.' % i
            gw, gx = backward(kinds(cfg, i), layer_leaves(p, i), xs.pop(),
                              seg, gx)
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
