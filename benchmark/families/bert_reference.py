"""Plain reference for the `bert` family: BERT pre-training, written from the
papers, in `jax.numpy` and float32. It imports nothing of the program
(`harness.rounding` is the benchmark's own).

- Devlin et al. 2018 (arXiv:1810.04805) section 3 and A.2: token + position +
  segment embeddings, LayerNorm, post-LN encoder blocks (self-attention with
  1/sqrt(d) scaling, GELU feed-forward), a tanh pooler on the first token,
  the masked-LM head (dense + GELU + LayerNorm + the tied embedding matrix +
  a bias) on the masked positions only, the next-sentence head, the sum of
  the two mean cross-entropies.
- Loshchilov & Hutter 2019 (arXiv:1711.05101) algorithm 2 for AdamW, with
  the decay on every parameter (the configuration's `assumed` says so).

Departures from the paper, each because the configuration states it:
dropout is off (the comparison needs the same arithmetic on both sides);
LayerNorm's epsilon is 1e-12 everywhere as in `google-research/bert` (the
program's encoder blocks use 1e-5, a relative difference of 5e-6).

The parameters come in as a dict under the names `bert.param_spec` gives
them; weight matrices are [in, out].

`precision`:
- 'float32'  the reference: every matmul at `highest`.
- 'float8'   the CONTROL: matmul operands rounded to 4 exponent and 3
             mantissa bits (float8 e4m3) with a per-tensor scale, the usual
             fp8 recipe and the nearest precision below the stated one.
             `correct` has to come out false for it.
"""
import functools

import jax
import jax.numpy as jnp

from harness.rounding import leaf_norms, round_to
import numpy as np

LN_EPS = 1e-12
ROW_BLOCK_TOKENS = 2048     # rows per block = this // seq: bounds activations


def _mm(a, b, precision):
    return jnp.matmul(round_to(a, precision), round_to(b, precision),
                      precision=jax.lax.Precision.HIGHEST)


def _layer_norm(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * g + b


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / np.sqrt(2.0)))


_LAYER_LEAVES = ('self_attn.q_proj.weight', 'self_attn.q_proj.bias',
                 'self_attn.k_proj.weight', 'self_attn.k_proj.bias',
                 'self_attn.v_proj.weight', 'self_attn.v_proj.bias',
                 'self_attn.out_proj.weight', 'self_attn.out_proj.bias',
                 'norm1.weight', 'norm1.bias',
                 'linear1.weight', 'linear1.bias',
                 'linear2.weight', 'linear2.bias',
                 'norm2.weight', 'norm2.bias')


def _encoder_layer(heads, precision, x, bias, w):
    B, L, H = x.shape
    d = H // heads

    def split(t):
        return t.reshape(B, L, heads, d).transpose(0, 2, 1, 3)

    q = split(_mm(x, w['self_attn.q_proj.weight'], precision)
              + w['self_attn.q_proj.bias'])
    k = split(_mm(x, w['self_attn.k_proj.weight'], precision)
              + w['self_attn.k_proj.bias'])
    v = split(_mm(x, w['self_attn.v_proj.weight'], precision)
              + w['self_attn.v_proj.bias'])
    s = jnp.einsum('bhqd,bhkd->bhqk', round_to(q, precision),
                   round_to(k, precision),
                   precision=jax.lax.Precision.HIGHEST) / np.sqrt(d) + bias
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum('bhqk,bhkd->bhqd', round_to(p, precision),
                   round_to(v, precision),
                   precision=jax.lax.Precision.HIGHEST)
    a = a.transpose(0, 2, 1, 3).reshape(B, L, H)
    a = _mm(a, w['self_attn.out_proj.weight'], precision) \
        + w['self_attn.out_proj.bias']
    x = _layer_norm(x + a, w['norm1.weight'], w['norm1.bias'])
    f = _gelu(_mm(x, w['linear1.weight'], precision) + w['linear1.bias'])
    f = _mm(f, w['linear2.weight'], precision) + w['linear2.bias']
    return _layer_norm(x + f, w['norm2.weight'], w['norm2.bias'])


def block_loss(params, block, *, layers, heads, precision, mlm_denominator,
               nsp_denominator):
    """This block of rows' share of the batch's loss: summed cross-entropies
    over the WHOLE batch's denominators, so blocks add up to the batch's loss
    and their gradients to its gradient."""
    ids, segments, mask, positions, mlm_labels, nsp_labels = block
    B, L = ids.shape
    emb = 'bert.embeddings.'
    x = (params[emb + 'word_embeddings.weight'][ids]
         + params[emb + 'position_embeddings.weight'][:L][None]
         + params[emb + 'token_type_embeddings.weight'][segments])
    x = _layer_norm(x, params[emb + 'layer_norm.weight'],
                    params[emb + 'layer_norm.bias'])
    bias = ((1.0 - mask.astype(jnp.float32)) * -1e4)[:, None, None, :]
    stacked = {leaf: jnp.stack([params['bert.encoder.layers.%d.%s' % (i, leaf)]
                                for i in range(layers)])
               for leaf in _LAYER_LEAVES}
    layer = jax.checkpoint(functools.partial(_encoder_layer, heads, precision))
    x, _ = jax.lax.scan(lambda h, w: (layer(h, bias, w), None), x, stacked)
    pooled = jnp.tanh(_mm(x[:, 0], params['bert.pooler.dense.weight'],
                          precision) + params['bert.pooler.dense.bias'])
    picked = jnp.take_along_axis(x, positions[:, :, None], axis=1)
    h = _gelu(_mm(picked, params['cls.transform.weight'], precision)
              + params['cls.transform.bias'])
    h = _layer_norm(h, params['cls.layer_norm.weight'],
                    params['cls.layer_norm.bias'])
    logits = _mm(h, params[emb + 'word_embeddings.weight'].T, precision) \
        + params['cls.decoder_bias']
    logp = jax.nn.log_softmax(logits, axis=-1)
    valid = mlm_labels >= 0
    nll = -jnp.take_along_axis(logp, jnp.where(valid, mlm_labels, 0)[..., None],
                               axis=-1)[..., 0]
    mlm = jnp.sum(jnp.where(valid, nll, 0.0)) / mlm_denominator
    nsp_logits = _mm(pooled, params['cls.seq_relationship.weight'],
                     precision) + params['cls.seq_relationship.bias']
    nsp_logp = jax.nn.log_softmax(nsp_logits, axis=-1)
    nsp = -jnp.sum(jnp.take_along_axis(nsp_logp, nsp_labels.reshape(-1, 1),
                                       axis=-1)) / nsp_denominator
    return mlm + nsp


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
    ((ids, segments, mask, positions), (mlm_labels, nsp_labels)).
    Returns {'losses': [...], 'first_gradient': {leaf: array, on the host},
    'change_norms': {leaf: norm of (params after the steps - params)}}.
    """
    layers, heads = cfg['num_hidden_layers'], cfg['num_attention_heads']
    rows = batches[0][0][0].shape[0]
    seq = batches[0][0][0].shape[1]
    per_block = max(1, min(rows, ROW_BLOCK_TOKENS // seq))
    while rows % per_block:
        per_block -= 1

    @jax.jit
    def loss_and_grad(p, batch):
        (ids, seg, mask, pos), (mlm_labels, nsp_labels) = batch
        parts = (ids, seg, mask, pos, mlm_labels, nsp_labels)
        kw = dict(layers=layers, heads=heads, precision=precision,
                  mlm_denominator=jnp.maximum(
                      jnp.sum(mlm_labels >= 0), 1).astype(jnp.float32),
                  nsp_denominator=float(rows))
        blocks = tuple(v.reshape((rows // per_block, per_block) + v.shape[1:])
                       for v in parts)

        def one(acc, block):
            loss, grad = jax.value_and_grad(block_loss)(p, block, **kw)
            return (acc[0] + loss,
                    jax.tree_util.tree_map(jnp.add, acc[1], grad)), None

        zero = (jnp.float32(0.0), jax.tree_util.tree_map(jnp.zeros_like, p))
        (loss, grad), _ = jax.lax.scan(one, zero, blocks)
        return loss, grad

    @functools.partial(jax.jit, donate_argnums=(0, 2))
    def update(p, g, m, t):
        return adamw_update(p, g, m, t, lr=optim['learning_rate'],
                            beta1=optim['beta1'], beta2=optim['beta2'],
                            eps=optim['epsilon'],
                            weight_decay=optim['weight_decay'])

    change = jax.jit(lambda a, b: leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, a, b)))
    start = params
    p = jax.tree_util.tree_map(jnp.copy, params)
    moments = {k: (jnp.zeros_like(v), jnp.zeros_like(v))
               for k, v in params.items()}
    out = {'losses': []}
    for t, batch in enumerate(batches, 1):
        loss, grad = loss_and_grad(p, batch)
        out['losses'].append(float(loss))
        if t == 1:
            out['first_gradient'] = jax.device_get(grad)
        p, moments = update(p, grad, moments, jnp.float32(t))
    out['change_norms'] = jax.device_get(change(p, start))
    return out
