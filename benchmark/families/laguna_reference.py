"""Plain reference for the `laguna` family: causal-LM training of the
Laguna-XS.2 decoder (`poolside/Laguna-XS.2`, `model_type` `laguna`) on packed
rows, written from the published configuration's keys in `jax.numpy` and
float32. It imports nothing of the program (`harness.rounding` is the
benchmark's own).

x: a packed row, `seg` its document numbers, p_t = t minus the start of t's
document.

- The net: token embedding; `num_hidden_layers` blocks
  `h = x + Attn_i(RMSNorm(x)); out = h + FFN_i(RMSNorm(h))`, eps
  `rms_norm_eps`; a final RMSNorm; an untied head.
- Attention of layer i: H_i = `num_attention_heads_per_layer[i]` query heads
  of `head_dim` on `num_key_value_heads` K/V heads, no bias; query head h
  reads K/V head h // (H_i / K/V heads). With r =
  `rope_parameters[kind].partial_rotary_factor` x `head_dim` the channels
  that turn, q and k are rotated in the half-split form INSIDE their first r
  channels: rot(x)[:r] = x[:r] cos + [-x[r/2:r], x[:r/2]] sin, x[r:]
  unchanged, cos and sin of p_t * inv_freq_j (j < r / 2) repeated over the
  two halves of the r. `layer_types[i]`:
  'sliding_attention': r = `head_dim`, inv_freq_j = theta^(-2j / r); the
  query at p sees the keys max(0, p - sliding_window + 1) .. p of its
  document (its own position counts among the `sliding_window`).
  'full_attention': r = `head_dim` / 2; every key of its document up to p;
  YaRN of DIMENSION r as the public `rope_type: yarn` computes it:
  e_j = theta^(-2j / r), c(b) = r ln(original / (2 pi b)) / (2 ln theta),
  low = floor(c(beta_fast)), high = ceil(c(beta_slow)),
  ramp_j = clip((j - low) / (high - low), 0, 1),
  inv_freq_j = (e_j / factor) ramp_j + e_j (1 - ramp_j); cos and sin both
  times `attention_factor` (on the r turned channels only).
  Then softmax(q k^T / sqrt(head_dim)); the output gate, one scalar a query
  head and token: o_h <- sigmoid(RMSNorm(x) W_g)_h o_h (`gating`); W_o. The
  whole score matrix of a head with the window and the document masks
  written out, one head and one row at a time, so that 8192 positions fit.
- FFN of layer i, `mlp_layer_types[i]`: 'dense': SwiGLU of
  `intermediate_size`. 'sparse': s = sigmoid(x W_r) over all
  `num_experts_total` in float32; the `num_experts_per_tok` largest (the
  correction bias is zero); weights s_e / (the picks' sum) times
  `moe_routed_scaling_factor`, on the experts' OUTPUT; THIS CHIP'S SHARE of
  the routed sum, the experts `experts_held`: y = sum over held e of w_e
  down_e(silu(gate_e x) * up_e x), as plain dense products over the held
  experts, each over every token, masked; plus one shared SwiGLU expert of
  `shared_expert_intermediate_size` for every token. The routing is the
  reference's own: it is not handed the program's.
- L = mean cross-entropy of RMSNorm(x) W_head against the next id over the
  positions whose next id lies in the same document.

Departures, each stated by the configuration's `assumed`: pre-norm blocks
(the family's convention; the config has no key); the gate's form (a scalar
a head: the sibling configuration's `gating: "per-head"` and the parameter
count) and its sigmoid; the router's sigmoid scoring with renormalised
picks; no q/k norm, no auxiliary loss, no prediction module (no key for any
of them); positions restart at each document; AdamW at a constant rate on
every parameter; weights from the seed.

`precision`:
- 'float32'  the reference: every product at `highest`.
- 'float8'   the CONTROL: the operands of every matrix product (the
             projections, the gate's among them, the scores and the weighted
             values, the dense layer, the experts, the head) rounded to e4m3
             with a per-tensor scale; the router, the rotation, the
             sigmoids and the norms stay in float32, as they do in the
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
    """-> (inv_freq (r / 2,) float32, the factor on cos and sin) of the
    layer kind `kind`, from `rope_parameters`; r the channels that turn."""
    r = cfg['rope_parameters'][kind]
    d = int(cfg['head_dim'] * r['partial_rotary_factor'])
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
    """x (B, T, H, d), pos (B, T): the first r = 2 len(inv_freq) channels
    turned, [x1, x2] cos + [-x2, x1] sin with x1, x2 THEIR two halves; the
    channels behind them as they are."""
    half = len(inv_freq)
    angle = pos.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq)
    cos = factor * jnp.cos(angle)[:, :, None, :]
    sin = factor * jnp.sin(angle)[:, :, None, :]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def layer_of(cfg, i):
    """Layer i -> (its kind, its query heads, 'dense' or 'sparse')."""
    return (cfg['layer_types'][i], cfg['num_attention_heads_per_layer'][i],
            cfg['mlp_layer_types'][i])


def _attention(cfg, layer, w, x, seg, precision):
    B, T, _ = x.shape
    kind, H, _ = layer
    HK, D = cfg['num_key_value_heads'], cfg['head_dim']
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
    gate = jax.nn.sigmoid(_mm(x, w['g_proj'], precision))        # (B, T, H)
    o = jnp.moveaxis(o, 0, 2) * gate[..., None]
    return _mm(o.reshape(B, T, H * D), w['o_proj'], precision)


def _swiglu(x, gate, up, down, precision):
    return _mm(jax.nn.silu(_mm(x, gate, precision)) * _mm(x, up, precision),
               down, precision)


def route(cfg, w, x):
    """-> (idx (..., k), weights (..., k)): float32 whatever `precision`."""
    s = jax.nn.sigmoid(jnp.matmul(x, w['mlp.router'], precision=HIGH))
    picked, idx = jax.lax.top_k(s, cfg['num_experts_per_tok'])
    return idx, picked / jnp.sum(picked, -1, keepdims=True) \
        * cfg['moe_routed_scaling_factor']


def _moe(cfg, w, x, precision):
    """This chip's share of the routed sum and the shared expert."""
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


def _ffn(cfg, layer, w, x, precision):
    """The dense SwiGLU where `mlp_layer_types` says so, else the expert
    layer."""
    if layer[2] == 'sparse':
        return _moe(cfg, w, x, precision)
    return _swiglu(x, w['mlp.gate_proj'], w['mlp.up_proj'],
                   w['mlp.down_proj'], precision)


def attend(cfg, layer, precision, w, x, seg):
    """x + Attn(RMSNorm(x)): the first half of a block, a row at a time (a
    row's temporaries are let go before the next row's are made)."""
    attention = {k[10:]: v for k, v in w.items() if k.startswith('attention.')}

    @jax.checkpoint
    def row(xs):
        x, seg = xs[0][None], xs[1][None]
        h = _rms_norm(x, w['input_norm.weight'], cfg['rms_norm_eps'])
        return (x + _attention(cfg, layer, attention, h, seg, precision))[0]

    return jax.lax.map(row, (x, seg))


def block(cfg, layer, precision, w, x, seg):
    """One decoder block, `layer` what `layer_of` says of it; `w` holds its
    leaves without their prefix."""
    x = attend(cfg, layer, precision, w, x, seg)
    h = _rms_norm(x, w['post_attention_norm.weight'], cfg['rms_norm_eps'])
    return x + _ffn(cfg, layer, w, h, precision)


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
    ((ids, segment_ids, labels), ()). The routers' correction biases are
    zero, as the configuration states, and are not asked for. Returns
    {'losses': [L, ...], 'first_gradient': {leaf: array, on the host},
    'change_norms': {leaf: norm of (params after the steps - params)}}.
    `routing`, a list, is given the first step's selected experts, one
    (B, T, k) array per SPARSE layer, sorted along k.
    """
    layers = cfg['num_hidden_layers']
    stack = [layer_of(cfg, i) for i in range(layers)]
    eps = cfg['rms_norm_eps']
    start = jax.device_get(params)          # on the host until the end
    p = dict(params)
    del params
    moments = {k: (jnp.zeros_like(v), jnp.zeros_like(v))
               for k, v in p.items()}

    @functools.partial(jax.jit, static_argnums=0)
    def forward(layer, w, x, seg):
        return block(cfg, layer, precision, w, x, seg)

    @functools.partial(jax.jit, static_argnums=0)
    def backward(layer, w, x, seg, gx):
        _, vjp = jax.vjp(lambda w, x: block(cfg, layer, precision, w, x, seg),
                         w, x)
        return vjp(gx)

    @functools.partial(jax.jit, static_argnums=0)
    def selected(layer, w, x, seg):
        x = attend(cfg, layer, precision, w, x, seg)
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
            if t == 1 and routing is not None and stack[i][2] == 'sparse':
                routing.append(np.asarray(selected(stack[i], w, xs[-1],
                                                   seg)))
            xs.append(forward(stack[i], w, xs[-1], seg))
        loss, (g_scale, g_head, gx) = head(p['norm.weight'], p['lm_head'],
                                           xs.pop(), labels)
        out['losses'].append(float(loss))
        apply({'norm.weight': g_scale, 'lm_head': g_head}, t)
        del g_scale, g_head
        for i in reversed(range(layers)):
            prefix = 'layers.%d.' % i
            gw, gx = backward(stack[i], leaves_under(p, prefix), xs.pop(),
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
