"""Plain reference for the `joyai_llm_flash` family: causal-LM training of the
JoyAI-LLM-Flash decoder with its multi-token-prediction module on packed
rows, written from the DeepSeek-V3 report (arXiv:2412.19437 sections 2.1-2.2,
whose keys the published configuration carries) in `jax.numpy` and float32.
It imports nothing of the program (`harness.rounding` is the benchmark's own).

x: a packed row, `seg` its document numbers, p_t = t minus the start of t's
document.

- The net: token embedding; blocks `x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x))`
  with eps `rms_norm_eps`; a final RMSNorm; an untied head.
- Attention, `num_attention_heads` heads: c_q = RMSNorm(x W_qa) (`q_lora_rank`);
  [q_nope, q_pe] = c_q W_qb per head; [c_kv, k_pe] = x W_kva
  (`kv_lora_rank` + rope), c_kv = RMSNorm(c_kv); [k_nope, v] = c_kv W_kvb per
  head; q = [q_nope, R(p_t) q_pe], k = [k_nope, R(p_t) k_pe] with the ONE
  rotated k_pe shared by the heads; R(p) turns each adjacent pair (2j, 2j+1)
  of the rope slice by the angle p * rope_theta^(-2j / rope), by explicit
  cos and sin (`rope_interleave`; no scaling of the base: `rope_scaling`
  null); softmax of q k^T / sqrt(nope + rope) over the keys of the same
  document up to the query; W_o. No bias. The whole score matrix of a head,
  one head and one row at a time, so that 8192 positions fit.
- FFN: SwiGLU of `intermediate_size` in the first `first_k_dense_replace`
  layers; after them the expert layer: s = sigmoid(x W_r) over all
  `num_experts_total` experts, the top `num_experts_per_tok` by
  s + e_score_correction_bias (zero here; `n_group` = `topk_group` = 1: no
  group limit), weights s_sel / sum(s_sel) * `routed_scaling_factor`, and
  THIS CHIP'S SHARE of the routed sum, the experts `experts_held` (a loop over
  the held experts, each over every token, masked), plus the shared expert.
  The routing is the reference's own: it is not handed the program's.
- The prediction module (`num_nextn_predict_layers` 1), h_t the main model's
  output at t after its final norm:
  h'_t = W_eh [RMSNorm_e(Emb(id_{t+1})) ; RMSNorm_h(h_t)], the embedding
  first (as the family's released checkpoints lay `eh_proj` out; the report's
  equation 21 prints [h ; Emb], a permutation of W_eh's rows);
  g = Block_mtp(h'), a block of the expert kind with its own weights, the same
  `seg` and positions; logits'_t = RMSNorm_mtp(g_t) W_head with the SHARED
  head, Emb the SHARED table; L_mtp = mean cross-entropy of logits'_t against
  id_{t+2} over the positions whose t+1 and t+2 lie in t's document (the
  batch's fourth array, -1 elsewhere). The row's last position has no id one
  ahead: it is given the row's first (it has no label, and no position with a
  label attends to it).
- L = L_main + lambda L_mtp, lambda `assumed_values.mtp_loss_weight`.

Departures, each stated by the configuration's `assumed`: lambda (config.json
has no key for it); which h feeds the module (after the final norm); AdamW at
a constant rate on every parameter; weights from the seed.

`precision`:
- 'float32'  the reference: every product at `highest`.
- 'float8'   the CONTROL: the operands of every matrix product (the
             projections, the scores and the weighted values, the experts,
             `eh_proj`, the head) rounded to e4m3 with a per-tensor scale;
             the router, the rotation and the norms stay in float32, as they
             do in the program. `correct` has to come out false for it.

Memory: a step is taken block by block. The forward pass keeps each block's
input; the backward pass walks from the module's head down (the module, the
main head, the blocks), takes one piece's gradient with `jax.vjp`, hands it
to AdamW and lets it go; the head's and the embedding's two gradients (one
from each loss) are added before their one update.
"""
import functools

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


def _rotate(x, pos, theta):
    """x (B, T, ..., d), pos (B, T): the pair (x_2j, x_2j+1) turned by
    pos * theta^(-2j / d)."""
    d = x.shape[-1]
    angle = pos.astype(jnp.float32)[..., None] \
        * theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    while angle.ndim < x.ndim:
        angle = angle[:, :, None]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.zeros_like(x)
    out = out.at[..., 0::2].set(a * cos - b * sin)
    return out.at[..., 1::2].set(a * sin + b * cos)


def _mla(cfg, w, x, seg, precision):
    B, T, _ = x.shape
    H = cfg['num_attention_heads']
    nope, rope = cfg['qk_nope_head_dim'], cfg['qk_rope_head_dim']
    dv, rank, eps = cfg['v_head_dim'], cfg['kv_lora_rank'], cfg['rms_norm_eps']
    pos = positions(seg)
    c_q = _rms_norm(_mm(x, w['q_a_proj'], precision), w['q_a_norm'], eps)
    q = _mm(c_q, w['q_b_proj'], precision).reshape(B, T, H, nope + rope)
    kva = _mm(x, w['kv_a_proj'], precision)
    c_kv = _rms_norm(kva[..., :rank], w['kv_a_norm'], eps)
    kv = _mm(c_kv, w['kv_b_proj'], precision).reshape(B, T, H, nope + dv)
    q = jnp.concatenate([q[..., :nope],
                         _rotate(q[..., nope:], pos, cfg['rope_theta'])], -1)
    k_pe = _rotate(kva[..., rank:], pos, cfg['rope_theta'])      # (B, T, rope)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_pe[:, :, None, :], (B, T, H, rope))], axis=-1)
    v = kv[..., nope:]
    t = jnp.arange(T)
    sees = (seg[:, :, None] == seg[:, None, :]) \
        & (t[:, None] >= t[None, :])[None]                       # (B, T, T)

    @jax.checkpoint
    def head(xs):
        q, k, v = xs                                             # (B, T, d)
        s = _einsum('bqd,bkd->bqk', q, k, precision) / np.sqrt(nope + rope)
        p = jax.nn.softmax(jnp.where(sees, s, -1e30), axis=-1)
        return _einsum('bqk,bkd->bqd', p, v, precision)

    o = jax.lax.map(head, tuple(jnp.moveaxis(a, 2, 0) for a in (q, k, v)))
    return _mm(jnp.moveaxis(o, 0, 2).reshape(B, T, H * dv), w['o_proj'],
               precision)


def _swiglu(x, gate, up, down, precision):
    return _mm(jax.nn.silu(_mm(x, gate, precision)) * _mm(x, up, precision),
               down, precision)


def route(cfg, w, x):
    """-> (idx (..., k), weights (..., k)): float32 whatever `precision`."""
    s = jax.nn.sigmoid(jnp.matmul(x, w['mlp.router'], precision=HIGH))
    _, idx = jax.lax.top_k(s + w['mlp.e_score_correction_bias'],
                           cfg['num_experts_per_tok'])
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


def attend(cfg, precision, w, x, seg):
    """x + Attn(RMSNorm(x)): the first half of a block, a row at a time (a
    row's temporaries are let go before the next row's are made)."""
    attention = {k[10:]: v for k, v in w.items() if k.startswith('attention.')}

    @jax.checkpoint
    def row(xs):
        x, seg = xs[0][None], xs[1][None]
        h = _rms_norm(x, w['input_norm.weight'], cfg['rms_norm_eps'])
        return (x + _mla(cfg, attention, h, seg, precision))[0]

    return jax.lax.map(row, (x, seg))


def block(cfg, precision, w, x, seg):
    """One decoder block; `w` holds its leaves without their prefix (with the
    router's bias beside them where it has a router: `with_buffers`)."""
    x = attend(cfg, precision, w, x, seg)
    h = _rms_norm(x, w['post_attention_norm.weight'], cfg['rms_norm_eps'])
    if 'mlp.router' in w:
        return x + _moe(cfg, w, h, precision)
    return x + _swiglu(h, w['mlp.gate_proj'], w['mlp.up_proj'],
                       w['mlp.down_proj'], precision)


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


def join(cfg, precision, w, e, h):
    """h' = W_eh [RMSNorm_e(e) ; RMSNorm_h(h)]."""
    eps = cfg['rms_norm_eps']
    return _mm(jnp.concatenate([_rms_norm(e, w['mtp.enorm.weight'], eps),
                                _rms_norm(h, w['mtp.hnorm.weight'], eps)],
                               axis=-1), w['mtp.eh_proj'], precision)


def leaves_under(params, prefix):
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
                 routing=None, parts=None):
    """Follow the first len(batches) optimizer steps from `params`.

    `batches` are the host batches the program was fed, each
    ((ids, segment_ids, labels, labels_ahead), ()). Returns {'losses':
    [L, ...], 'first_gradient': {leaf: array, on the host}, 'change_norms':
    {leaf: norm of (params after the steps - params)}}. `routing`, a list,
    is given the first step's selected experts, one (B, T, k) array per
    expert layer (the module's last), sorted along k; `parts`, a list, each
    step's (L_main, L_mtp).
    """
    if cfg['num_nextn_predict_layers'] != 1:
        raise ValueError('the reference follows one prediction module')
    layers = cfg['num_hidden_layers']
    lam = cfg['assumed_values']['mtp_loss_weight']
    eps = cfg['rms_norm_eps']
    start = jax.device_get(params)          # on the host until the end
    p = dict(params)
    del params
    moments = {k: (jnp.zeros_like(v), jnp.zeros_like(v))
               for k, v in p.items()}

    @jax.jit
    def forward(w, x, seg):
        return block(cfg, precision, with_buffers(cfg, w), x, seg)

    @jax.jit
    def backward(w, x, seg, gx):
        _, vjp = jax.vjp(lambda w, x: block(
            cfg, precision, with_buffers(cfg, w), x, seg), w, x)
        return vjp(gx)

    @jax.jit
    def selected(w, x, seg):
        w = with_buffers(cfg, w)
        x = attend(cfg, precision, w, x, seg)
        h = _rms_norm(x, w['post_attention_norm.weight'], eps)
        return jnp.sort(route(cfg, w, h)[0], axis=-1)

    @jax.jit
    def normed(scale, x):
        return _rms_norm(x, scale, eps)

    @jax.jit
    def normed_backward(scale, x, gh):
        return jax.vjp(lambda s, x: _rms_norm(x, s, eps), scale, x)[1](gh)

    @jax.jit
    def head(table, h, labels, weight):
        """-> (the head's loss of h, already normed; weight times its
        gradients by the head and by h)."""
        loss, grads = jax.value_and_grad(
            functools.partial(head_loss, precision), argnums=(0, 1))(
                table, h, labels)
        return (loss,) + tuple(weight * g for g in grads)

    @jax.jit
    def joined(w, e, h):
        return join(cfg, precision, w, e, h)

    @jax.jit
    def joined_backward(w, e, h, gx):
        return jax.vjp(lambda w, e, h: join(cfg, precision, w, e, h),
                       w, e, h)[1](gx)

    @jax.jit
    def embedding_gradient(table, ids, gx, ids_ahead, ge):
        return jnp.zeros_like(table).at[ids].add(gx).at[ids_ahead].add(ge)

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

    def block_backward(prefix, x, seg, gx, t):
        gw, gx = backward(leaves_under(p, prefix), x, seg, gx)
        apply({prefix + k: v for k, v in gw.items()}, t)
        return gx

    for t, ((ids, seg, labels, labels_ahead), _) in enumerate(batches, 1):
        ids, seg, labels, labels_ahead = (
            jnp.asarray(v) for v in (ids, seg, labels, labels_ahead))
        ids_ahead = jnp.roll(ids, -1, axis=1)
        table = p['embed_tokens.weight']
        xs = [table[ids]]
        picks = []
        for i in range(layers):
            w = leaves_under(p, 'layers.%d.' % i)
            if t == 1 and routing is not None and 'mlp.router' in w:
                picks.append(np.asarray(selected(w, xs[-1], seg)))
            xs.append(forward(w, xs[-1], seg))
        h = normed(p['norm.weight'], xs[-1])
        e = table[ids_ahead]
        joint = {k: p[k] for k in ('mtp.enorm.weight', 'mtp.hnorm.weight',
                                   'mtp.eh_proj')}
        x_mtp = joined(joint, e, h)
        w = leaves_under(p, 'mtp.block.')
        if t == 1 and routing is not None:
            routing.extend(picks + [np.asarray(selected(w, x_mtp, seg))])
        g = forward(w, x_mtp, seg)
        # the module, from its head down
        l_mtp, g_head, gg = head(p['lm_head'],
                                 normed(p['mtp.norm.weight'], g),
                                 labels_ahead, lam)
        g_scale, gx = normed_backward(p['mtp.norm.weight'], g, gg)
        del g, gg
        apply({'mtp.norm.weight': g_scale}, t)
        gx = block_backward('mtp.block.', x_mtp, seg, gx, t)
        del x_mtp
        gw, ge, gh = joined_backward(joint, e, h, gx)
        apply(gw, t)
        del gw, joint, e
        # the main head; the head's two gradients (one from each loss) are
        # added before its one update, as are h's in front of the final norm
        l_main, g_main, gh_main = head(p['lm_head'], h, labels, 1.0)
        apply({'lm_head': g_head + g_main}, t)
        del g_head, g_main, h
        out['losses'].append(float(l_main) + lam * float(l_mtp))
        if parts is not None:
            parts.append((float(l_main), float(l_mtp)))
        g_scale, gx = normed_backward(p['norm.weight'], xs.pop(),
                                      gh + gh_main)
        del gh, gh_main
        apply({'norm.weight': g_scale}, t)
        for i in reversed(range(layers)):
            gx = block_backward('layers.%d.' % i, xs.pop(), seg, gx, t)
        apply({'embed_tokens.weight': embedding_gradient(
            p['embed_tokens.weight'], ids, gx, ids_ahead, ge)}, t)
        del gx, ge
    norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
    out['change_norms'] = {k: float(norm(p[k], jnp.asarray(start[k])))
                           for k in start}
    return out
