"""Plain reference for the `nemotron_h` family: causal-LM training of the
Nemotron-H-pattern decoder that the config.json of
`nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16` declares (`model_type`
`nemotron_h`) on packed rows, written from the published configuration's
keys in `jax.numpy` and float32. It imports nothing of the program
(`harness.rounding` is the benchmark's own).

x: a packed row, `seg` its document numbers.

- The net: token embedding; `num_hidden_layers` layers of ONE sublayer,
  `x = x + mixer_i(RMSNorm(x))`, eps `layer_norm_epsilon`, the mixer named by
  `hybrid_override_pattern[i]`; a final RMSNorm; an untied head.
- `M`, Mamba-2 (`mamba_num_heads` heads of `mamba_head_dim` channels,
  `n_groups` groups, a state of `ssm_state_size`): [z | xBC | dt] = u W_in,
  no bias; xBC = silu(conv(xBC) + b_conv), a depthwise causal convolution of
  `conv_kernel` taps written as that many shifted products, a tap dropped
  where it would reach into another document; [x | B | C] = xBC, head h
  reading B and C of group h // (heads / groups);
  dt = softplus(dt + dt_bias), A = -exp(A_log), a head. Per head the state
  S (head_dim x state), zero where a document starts, TOKEN BY TOKEN under
  `lax.scan` (no chunk form: the program's is then held to an independent
  one):  S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T,  y_t = S_t C_t + D x_t.
  Then y = RMSNorm_groups(y * silu(z)) * w, the gate BEFORE the norm, the
  mean of squares over each group's channels, eps `layer_norm_epsilon`;
  out = y W_out.
- `*`, attention: q = u W_q (`num_attention_heads` heads of `head_dim`),
  k = u W_k, v = u W_v (`num_key_value_heads`), no bias, NO rotation; query
  head h reads K/V head h // (heads / kv heads);
  softmax(q k^T / sqrt(head_dim)) over the keys of the query's document up
  to its own position; W_o. The whole score matrix of a head with the masks
  written out, one head and one row at a time, so that 8192 positions fit.
- `E`, experts: s = sigmoid(u W_r) over all `num_experts_total` in float32;
  the `num_experts_per_tok` largest by s + e_score_correction_bias (zero
  here); weights s_e / (the picks' sum) (`norm_topk_prob`) times
  `routed_scaling_factor`; THIS CHIP'S SHARE of the routed sum, the experts
  `experts_held`: y = sum over held e of w_e down_e(relu(up_e u)^2), as
  plain dense products over the held experts, each over every token, masked;
  plus one shared expert of the same form for every token. The routing is
  the reference's own: it is not handed the program's.
- L = mean cross-entropy of RMSNorm(x) W_head against the next id over the
  positions whose next id lies in the same document.

Departures, each stated by the configuration's `assumed`: no rotation in the
attention layers (the family's; `rope_theta` is carried and unused); the
residual stream in float32; `time_step_limit` (0, inf) clips nothing; no
auxiliary loss (no key for a coefficient); the denoiser tower of the
released model (adaLN, in-block bidirectional attention, cross-tower
conditioning, a diffusion loss) has no key in config.json and is NOT here;
AdamW at a constant rate on every parameter; weights from the seed.

`precision`:
- 'float32'  the reference: every product at `highest`.
- 'float8'   the CONTROL: the operands of every matrix product (the
             projections, x, B and C as the state-space rule reads them,
             the scores and the weighted values, the experts, the head)
             rounded to e4m3 with a per-tensor scale; the router, the
             decays, the convolution and the norms stay in float32, as they
             do in the program. `correct` has to come out false for it.

Memory: a step is taken layer by layer. The forward pass keeps each layer's
input; the backward pass walks from the head down, takes one layer's
gradient with `jax.vjp`, hands it to AdamW and lets it go. A Mamba-2 layer's
recurrence keeps the state at every `KEPT_EVERY` tokens and runs the tokens
between again in the backward pass (a state is 2 MB a row).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from harness.rounding import round_to

HIGH = jax.lax.Precision.HIGHEST
KINDS = {'M': 'mamba', '*': 'attention', 'E': 'experts'}
KEPT_EVERY = 128


def _mm(a, b, precision):
    return jnp.matmul(round_to(a, precision), round_to(b, precision),
                      precision=HIGH)


def _einsum(spec, a, b, precision):
    return jnp.einsum(spec, round_to(a, precision), round_to(b, precision),
                      precision=HIGH)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def kinds(cfg):
    return [KINDS[c] for c in
            cfg['hybrid_override_pattern'][:cfg['num_hidden_layers']]]


def _starts(seg):
    """(B, T) -> True where a document starts."""
    return jnp.concatenate([jnp.ones_like(seg[:, :1], bool),
                            seg[:, 1:] != seg[:, :-1]], axis=1)


def _conv(x, taps, bias, seg):
    """y_t = b + sum_i taps[n - 1 - i] x_{t - i}, i = 0 .. n - 1, a term
    dropped where t - i is before the row or in another document."""
    n, T = taps.shape[0], x.shape[1]
    t = jnp.arange(T)
    y = x * taps[n - 1]
    for i in range(1, n):
        back = jnp.roll(x, i, axis=1)
        same = (jnp.roll(seg, i, axis=1) == seg) & (t >= i)[None, :]
        y = y + jnp.where(same[..., None], back, 0.0) * taps[n - 1 - i]
    return y + bias


def _recurrence(x, dt, A, Bm, Cm, first):
    """One row, token by token. x (T, H, P); dt (T, H); A (H,); Bm, Cm
    (T, G, N), head h reading group h // (H / G); first (T,) -> (T, H, P).
    """
    T, H, P = x.shape
    G, N = Bm.shape[1:]

    def token(state, xs):
        x, dt, b, c, first = xs
        b, c = (jnp.repeat(a, H // G, axis=0) for a in (b, c))   # (H, N)
        state = jnp.where(first, 0.0, state)
        state = jnp.exp(dt * A)[:, None, None] * state \
            + (dt[:, None] * x)[:, :, None] * b[:, None, :]
        return state, jnp.sum(state * c[:, None, :], axis=-1)

    @jax.checkpoint
    def stretch(state, xs):
        return jax.lax.scan(token, state, xs)

    every = KEPT_EVERY if T % KEPT_EVERY == 0 else T
    xs = tuple(a.reshape((T // every, every) + a.shape[1:])
               for a in (x, dt, Bm, Cm, first))
    _, y = jax.lax.scan(stretch, jnp.zeros((H, P, N), jnp.float32), xs)
    return y.reshape(T, H, P)


def _mamba(cfg, w, u, seg, precision):
    B, T, _ = u.shape
    H, P = cfg['mamba_num_heads'], cfg['mamba_head_dim']
    G, N = cfg['n_groups'], cfg['ssm_state_size']
    inner, bc = H * P, G * N
    zxbcdt = _mm(u, w['in_proj'], precision)
    z, xbc, dt = (zxbcdt[..., :inner], zxbcdt[..., inner:2 * inner + 2 * bc],
                  zxbcdt[..., 2 * inner + 2 * bc:])
    xbc = jax.nn.silu(_conv(xbc, w['conv_weight'], w['conv_bias'], seg))
    x = xbc[..., :inner].reshape(B, T, H, P)
    Bm = xbc[..., inner:inner + bc].reshape(B, T, G, N)
    Cm = xbc[..., inner + bc:].reshape(B, T, G, N)
    dt = jax.nn.softplus(dt + w['dt_bias'])
    A = -jnp.exp(w['A_log'])
    y = jax.vmap(_recurrence, in_axes=(0, 0, None, 0, 0, 0))(
        round_to(x, precision), dt, A, round_to(Bm, precision),
        round_to(Cm, precision), _starts(seg))
    y = y + w['D'][:, None] * x
    y = (y.reshape(B, T, inner) * jax.nn.silu(z)).reshape(B, T, G, inner // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + cfg['layer_norm_epsilon'])
    return _mm(y.reshape(B, T, inner) * w['norm'], w['out_proj'], precision)


def _attention(cfg, w, u, seg, precision):
    B, T, _ = u.shape
    H, HK, D = (cfg['num_attention_heads'], cfg['num_key_value_heads'],
                cfg['head_dim'])
    q = _mm(u, w['q_proj'], precision).reshape(B, T, H, D)
    k = _mm(u, w['k_proj'], precision).reshape(B, T, HK, D)
    v = _mm(u, w['v_proj'], precision).reshape(B, T, HK, D)
    t = jnp.arange(T)
    sees = (seg[:, :, None] == seg[:, None, :]) \
        & (t[:, None] >= t[None, :])[None]                       # (B, T, T)
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


def _relu2(x, up, down, precision):
    return _mm(jnp.square(jax.nn.relu(_mm(x, up, precision))), down,
               precision)


def route(cfg, w, u):
    """-> (idx (..., k), weights (..., k)): float32 whatever `precision`;
    the correction bias is zero and is left out."""
    s = jax.nn.sigmoid(jnp.matmul(u, w['router'], precision=HIGH))
    _, idx = jax.lax.top_k(s, cfg['num_experts_per_tok'])
    picked = jnp.take_along_axis(s, idx, axis=-1)
    return idx, picked / jnp.sum(picked, -1, keepdims=True) \
        * cfg['routed_scaling_factor']


def _moe(cfg, w, u, precision):
    lo, hi = cfg['experts_held']
    idx, weights = route(cfg, w, u)
    expert = jax.checkpoint(functools.partial(_relu2, precision=precision))
    y = expert(u, w['shared.up_proj'], w['shared.down_proj'])
    for e in range(lo, hi):
        share = jnp.sum(jnp.where(idx == e, weights, 0.0), axis=-1)
        y = y + share[..., None] * expert(u, w['experts_up'][e - lo],
                                          w['experts_down'][e - lo])
    return y


def layer(cfg, kind, precision, w, x, seg):
    """x + mixer(RMSNorm(x)): one layer of the kind `kind`; `w` holds its
    leaves without their prefix. The Mamba-2 and attention layers a row at a
    time (a row's temporaries are let go before the next row's are made)."""
    mixer = {k[6:]: v for k, v in w.items() if k.startswith('mixer.')}
    eps = cfg['layer_norm_epsilon']
    if kind == 'experts':
        return x + _moe(cfg, mixer, _rms_norm(x, w['norm.weight'], eps),
                        precision)
    one = _mamba if kind == 'mamba' else _attention

    @jax.checkpoint
    def row(xs):
        x, seg = xs[0][None], xs[1][None]
        return (x + one(cfg, mixer, _rms_norm(x, w['norm.weight'], eps), seg,
                        precision))[0]

    return jax.lax.map(row, (x, seg))


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
    first step's selected experts, one (B, T, k) array per EXPERT layer,
    sorted along k.
    """
    layer_kinds = kinds(cfg)
    eps = cfg['layer_norm_epsilon']
    start = jax.device_get(params)          # on the host until the end
    p = dict(params)
    del params
    moments = {k: (jnp.zeros_like(v), jnp.zeros_like(v))
               for k, v in p.items()}

    @functools.partial(jax.jit, static_argnums=0)
    def forward(kind, w, x, seg):
        return layer(cfg, kind, precision, w, x, seg)

    @functools.partial(jax.jit, static_argnums=0)
    def backward(kind, w, x, seg, gx):
        _, vjp = jax.vjp(lambda w, x: layer(cfg, kind, precision, w, x, seg),
                         w, x)
        return vjp(gx)

    @jax.jit
    def selected(w, x):
        u = _rms_norm(x, w['norm.weight'], eps)
        return jnp.sort(route(cfg, leaves_under(w, 'mixer.'), u)[0], axis=-1)

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
        for i, kind in enumerate(layer_kinds):
            w = leaves_under(p, 'layers.%d.' % i)
            if t == 1 and routing is not None and kind == 'experts':
                routing.append(np.asarray(selected(w, xs[-1])))
            xs.append(forward(kind, w, xs[-1], seg))
        loss, (g_scale, g_head, gx) = head(p['norm.weight'], p['lm_head'],
                                           xs.pop(), labels)
        out['losses'].append(float(loss))
        apply({'norm.weight': g_scale, 'lm_head': g_head}, t)
        del g_scale, g_head
        for i in reversed(range(len(layer_kinds))):
            prefix = 'layers.%d.' % i
            gw, gx = backward(layer_kinds[i], leaves_under(p, prefix),
                              xs.pop(), seg, gx)
            apply({prefix + k: v for k, v in gw.items()}, t)
            del gw
        apply({'embed_tokens.weight': embedding_gradient(
            p['embed_tokens.weight'], ids, gx)}, t)
        del gx
    norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
    out['change_norms'] = {k: float(norm(p[k], jnp.asarray(start[k])))
                           for k in start}
    return out
