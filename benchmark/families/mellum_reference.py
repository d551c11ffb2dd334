"""Plain reference for the `mellum` family: causal-LM training of the Mellum 2
decoder (`JetBrains/Mellum2-12B-A2.5B-Instruct`, `model_type` `mellum`) on
packed rows, written from the published configuration's keys in `jax.numpy`
and float32. It imports nothing of the program (`harness.rounding` is the
benchmark's own).

x: a packed row, `seg` its document numbers, p_t = t minus the start of t's
document.

- The net: token embedding; `num_hidden_layers` blocks
  `h = x + Attn(RMSNorm(x)); out = h + MoE(RMSNorm(h))`, eps `rms_norm_eps`;
  a final RMSNorm; an untied head.
- Attention of layer i: q = x W_q (`num_attention_heads` heads of
  `head_dim`), k = x W_k, v = x W_v (`num_key_value_heads` heads), no bias;
  query head h reads K/V head h // (heads / kv heads). q and k are rotated
  over the whole head in the half-split form: rot(x) = x cos + [-x2, x1] sin,
  x1 and x2 the head's two halves, cos and sin of p_t * inv_freq_j repeated
  over the halves. `layer_types[i]`:
  'sliding_attention': inv_freq_j = theta^(-2j / head_dim); the query at p
  sees the keys max(0, p - sliding_window + 1) .. p of its document (its own
  position counts among the `sliding_window`).
  'full_attention': every key of its document up to p; YaRN as the public
  `rope_type: yarn` computes it from `rope_parameters.full_attention`:
  e_j = theta^(-2j / d), c(b) = d ln(original / (2 pi b)) / (2 ln theta),
  low = floor(c(beta_fast)), high = ceil(c(beta_slow)),
  ramp_j = clip((j - low) / (high - low), 0, 1),
  inv_freq_j = (e_j / factor) ramp_j + e_j (1 - ramp_j); cos and sin both
  times `attention_factor` (so the scores by its square).
  Then softmax(q k^T / sqrt(head_dim)) and W_o. The whole score matrix of a
  head with the window and the document masks written out, one head and one
  row at a time, so that 8192 positions fit.
- Experts, every layer: s = softmax(x W_r) over all `num_experts_total` in
  float32; the `num_experts_per_tok` largest; weights s_e / (the picks' sum)
  (`norm_topk_prob`); THIS CHIP'S SHARE of the routed sum, the experts
  `experts_held`: y = sum over held e of w_e down_e(silu(gate_e x) * up_e x),
  as plain dense products over the held experts, each over every token,
  masked. No shared expert, no bias, no scaling. The routing is the
  reference's own: it is not handed the program's.
- L = mean cross-entropy of RMSNorm(x) W_head against the next id over the
  positions whose next id lies in the same document.

Departures, each stated by the configuration's `assumed`: pre-norm blocks
(the family's convention; the config has no key); no q/k norm, no auxiliary
loss, no prediction module (no key for any of them); positions restart at
each document; AdamW at a constant rate on every parameter; weights from
the seed.

`precision`:
- 'float32'  the reference: every product at `highest`.
- 'float8'   the CONTROL: the operands of every matrix product (the
             projections, the scores and the weighted values, the experts,
             the head) rounded to e4m3 with a per-tensor scale; the router,
             the rotation and the norms stay in float32, as they do in the
             program. `correct` has to come out false for it.

Memory: a step is taken block by block. The forward pass keeps each block's
input; the backward pass walks from the head down, takes one piece's
gradient with `jax.vjp`, hands it to AdamW and lets it go.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from harness.rounding import round_to

HIGH = jax.lax.Precision.HIGHEST


def _mm(a, b, precision):
    return jnp.matmul(round_to(a, precision), round_to(b, precision),
                      precision=HIGH)


def _einsum(spec, a, b, precision):
    return jnp.einsum(spec, round_to(a, precision), round_to(b, precision),
                      precision=HIGH)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def positions(seg):
    """(B, T) document numbers -> each position's distance from the first
    position of its document."""
    t = jnp.arange(seg.shape[1])
    first = jnp.concatenate([jnp.ones_like(seg[:, :1], bool),
                             seg[:, 1:] != seg[:, :-1]], axis=1)
    return t[None, :] - jax.lax.cummax(jnp.where(first, t[None, :], 0), axis=1)


def rotary_table(cfg, kind):
    """-> (inv_freq (head_dim / 2,) float32, the factor on cos and sin) of
    the layer kind `kind`, from `rope_parameters`."""
    r, d = cfg['rope_parameters'][kind], cfg['head_dim']
    theta = float(r['rope_theta'])
    j = np.arange(d // 2, dtype=np.float64)
    e = theta ** (-2.0 * j / d)
    if r['rope_type'] == 'default':
        return e.astype(np.float32), 1.0
    if r['rope_type'] != 'yarn':
        raise ValueError('no rotary table of type %r' % (r['rope_type'],))

    def c(b):
        return d * math.log(r['original_max_position_embeddings']
                            / (2 * math.pi * b)) / (2 * math.log(theta))
    low = max(math.floor(c(r['beta_fast'])), 0)
    high = min(math.ceil(c(r['beta_slow'])), d - 1)
    ramp = np.clip((j - low) / (high - low), 0.0, 1.0)
    table = e / r['factor'] * ramp + e * (1.0 - ramp)
    return table.astype(np.float32), float(r['attention_factor'])


def _rotate(x, pos, inv_freq, factor):
    """x (B, T, H, d), pos (B, T): x cos + [-x2, x1] sin."""
    half = x.shape[-1] // 2
    angle = pos.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq)
    cos = factor * jnp.cos(angle)[:, :, None, :]
    sin = factor * jnp.sin(angle)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(cfg, kind, w, x, seg, precision):
    B, T, _ = x.shape
    H, HK, D = (cfg['num_attention_heads'], cfg['num_key_value_heads'],
                cfg['head_dim'])
    pos = positions(seg)
    inv_freq, factor = rotary_table(cfg, kind)
    q = _rotate(_mm(x, w['q_proj'], precision).reshape(B, T, H, D), pos,
                inv_freq, factor)
    k = _rotate(_mm(x, w['k_proj'], precision).reshape(B, T, HK, D), pos,
                inv_freq, factor)
    v = _mm(x, w['v_proj'], precision).reshape(B, T, HK, D)
    t = jnp.arange(T)
    sees = (seg[:, :, None] == seg[:, None, :]) \
        & (t[:, None] >= t[None, :])[None]                       # (B, T, T)
    if kind == 'sliding_attention':
        sees = sees & (t[:, None] - t[None, :] < cfg['sliding_window'])[None]
    k, v = (jnp.moveaxis(a, 2, 0) for a in (k, v))               # (HK, B, T, D)

    @jax.checkpoint
    def head(xs):
        q, h = xs                                                # (B, T, D)
        kv = h // (H // HK)
        s = _einsum('bqd,bkd->bqk', q, k[kv], precision) / np.sqrt(D)
        p = jax.nn.softmax(jnp.where(sees, s, -1e30), axis=-1)
        return _einsum('bqk,bkd->bqd', p, v[kv], precision)

    o = jax.lax.map(head, (jnp.moveaxis(q, 2, 0), jnp.arange(H)))
    return _mm(jnp.moveaxis(o, 0, 2).reshape(B, T, H * D), w['o_proj'],
               precision)


def _swiglu(x, gate, up, down, precision):
    return _mm(jax.nn.silu(_mm(x, gate, precision)) * _mm(x, up, precision),
               down, precision)


def route(cfg, w, x):
    """-> (idx (..., k), weights (..., k)): float32 whatever `precision`."""
    s = jax.nn.softmax(jnp.matmul(x, w['mlp.router'], precision=HIGH), -1)
    picked, idx = jax.lax.top_k(s, cfg['num_experts_per_tok'])
    return idx, picked / jnp.sum(picked, -1, keepdims=True)


def _moe(cfg, w, x, precision):
    lo, hi = cfg['experts_held']
    idx, weights = route(cfg, w, x)
    expert = jax.checkpoint(functools.partial(_swiglu, precision=precision))
    y = jnp.zeros_like(x)
    for e in range(lo, hi):
        share = jnp.sum(jnp.where(idx == e, weights, 0.0), axis=-1)
        y = y + share[..., None] * expert(
            x, w['mlp.experts_gate'][e - lo], w['mlp.experts_up'][e - lo],
            w['mlp.experts_down'][e - lo])
    return y


def attend(cfg, kind, precision, w, x, seg):
    """x + Attn(RMSNorm(x)): the first half of a block, a row at a time (a
    row's temporaries are let go before the next row's are made)."""
    attention = {k[10:]: v for k, v in w.items() if k.startswith('attention.')}

    @jax.checkpoint
    def row(xs):
        x, seg = xs[0][None], xs[1][None]
        h = _rms_norm(x, w['input_norm.weight'], cfg['rms_norm_eps'])
        return (x + _attention(cfg, kind, attention, h, seg, precision))[0]

    return jax.lax.map(row, (x, seg))


def block(cfg, kind, precision, w, x, seg):
    """One decoder block of the layer kind `kind`; `w` holds its leaves
    without their prefix."""
    x = attend(cfg, kind, precision, w, x, seg)
    h = _rms_norm(x, w['post_attention_norm.weight'], cfg['rms_norm_eps'])
    return x + _moe(cfg, w, h, precision)


def head_loss(precision, head, h, labels):
    """Mean over the positions that have a label of the cross-entropy of
    h W_head (h already normed), a row at a time."""
    count = jnp.maximum(jnp.sum(labels >= 0), 1).astype(jnp.float32)

    @jax.checkpoint
    def row(xs):
        h, labels = xs
        logp = jax.nn.log_softmax(_mm(h, head, precision), axis=-1)
        nll = -jnp.take_along_axis(logp, jnp.maximum(labels, 0)[:, None],
                                   axis=-1)[:, 0]
        return jnp.sum(jnp.where(labels >= 0, nll, 0.0))

    return jnp.sum(jax.lax.map(row, (h, labels))) / count


def leaves_under(params, prefix):
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


def follow_steps(cfg, optim, params, batches, precision='float32',
                 routing=None):
    """Follow the first len(batches) optimizer steps from `params`.

    `batches` are the host batches the program was fed, each
    ((ids, segment_ids, labels), ()). Returns {'losses': [L, ...],
    'first_gradient': {leaf: array, on the host}, 'change_norms': {leaf: norm
    of (params after the steps - params)}}. `routing`, a list, is given the
    first step's selected experts, one (B, T, k) array per layer, sorted
    along k.
    """
    layers = cfg['num_hidden_layers']
    kinds = cfg['layer_types'][:layers]
    eps = cfg['rms_norm_eps']
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

    @functools.partial(jax.jit, static_argnums=0)
    def selected(kind, w, x, seg):
        x = attend(cfg, kind, precision, w, x, seg)
        h = _rms_norm(x, w['post_attention_norm.weight'], eps)
        return jnp.sort(route(cfg, w, h)[0], axis=-1)

    @jax.jit
    def head(scale, table, x, labels):
        """-> (loss, its gradients by the final norm's scale, the head and
        x)."""
        return jax.value_and_grad(
            lambda s, w, x: head_loss(precision, w, _rms_norm(x, s, eps),
                                      labels), argnums=(0, 1, 2))(
                                          scale, table, x)

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

    def apply(grads, t):
        """AdamW on the leaves `grads` names (whole names); the first
        step's gradients go to the host."""
        if t == 1:
            out['first_gradient'].update(jax.device_get(grads))
        new_p, new_m = update({k: p[k] for k in grads}, grads,
                              {k: moments[k] for k in grads}, jnp.float32(t))
        p.update(new_p)
        moments.update(new_m)

    for t, ((ids, seg, labels), _) in enumerate(batches, 1):
        ids, seg, labels = (jnp.asarray(v) for v in (ids, seg, labels))
        xs = [p['embed_tokens.weight'][ids]]
        for i in range(layers):
            w = leaves_under(p, 'layers.%d.' % i)
            if t == 1 and routing is not None:
                routing.append(np.asarray(selected(kinds[i], w, xs[-1], seg)))
            xs.append(forward(kinds[i], w, xs[-1], seg))
        loss, (g_scale, g_head, gx) = head(p['norm.weight'], p['lm_head'],
                                           xs.pop(), labels)
        out['losses'].append(float(loss))
        apply({'norm.weight': g_scale, 'lm_head': g_head}, t)
        del g_scale, g_head
        for i in reversed(range(layers)):
            prefix = 'layers.%d.' % i
            gw, gx = backward(kinds[i], leaves_under(p, prefix), xs.pop(),
                              seg, gx)
            apply({prefix + k: v for k, v in gw.items()}, t)
            del gw
        apply({'embed_tokens.weight': embedding_gradient(
            p['embed_tokens.weight'], ids, gx)}, t)
        del gx
    norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
    out['change_norms'] = {k: float(norm(p[k], jnp.asarray(start[k])))
                           for k in start}
    return out
