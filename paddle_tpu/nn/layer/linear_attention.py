"""Attention layers of the decoders: Kimi Delta Attention (a gated delta
rule with a decay per channel, arXiv:2510.26692) and multi-head latent
attention, without position encoding (the full-attention layer the same paper
interleaves, one in four) or rotary with low-rank queries (arXiv:2412.19437
section 2.1); Gated DeltaNet (a decay per head, arXiv:2412.06464) and plain
multi-head causal attention with normed queries and keys (the OLMo 2/3
block's), either of which may hold a share of its heads
(docs/HEAD_SHARE.md); grouped-query attention with a rotary table over the
whole head or its first channels and, where asked, an attention window and
an output gate a head (docs/ATTENTION.md). All
take a packed row's document numbers: state, convolution, scores and
positions stop at document boundaries.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

from ...core.tensor import apply_op
from ...kernels.delta_rule import delta_rule
from ...kernels.flash_attention import attention_blhd
from ...kernels.rotary import (rope_inv_freq, rotary_halves, rotary_pairs,
                               rotate_halves, rotate_pairs, yarn_inv_freq)
from ...kernels.short_conv import short_conv
from ..initializer import Constant, Normal, ParamAttr
from ..layer_base import Layer
from ..functional.norm import rms_norm_values

__all__ = ['KimiDeltaAttention', 'LatentAttention', 'GatedDeltaNet',
           'CausalSelfAttention', 'GroupedQueryAttention', 'compute_dtype',
           'doc_starts', 'pre_normed', 'post_normed', 'rotate_pairs',
           'rotate_halves', 'rope_inv_freq', 'yarn_inv_freq']


def compute_dtype():
    """The operand type of the large products: amp's while `auto_cast` is
    entered, else None (as the operands come)."""
    from ...amp import amp_enabled
    st = amp_enabled()
    return st['dtype'] if st and st['enable'] else None


def _mm(x, w, dtype):
    if dtype is not None:
        x, w = x.astype(dtype), w.astype(dtype)
    return jnp.matmul(x, w)


def pre_normed(fn, pre_norm, recompute):
    """`fn(x, *rest)` with the block's RMSNorm in front of it, and the whole
    re-run in the backward pass where `recompute` says so (the block then
    keeps `x` alone, not the normed copy nor anything inside `fn`).
    -> (the function, the operands to put behind x)."""
    eps = pre_norm._epsilon if pre_norm is not None else None

    def run(x, *rest):
        if pre_norm is not None:
            x, rest = rms_norm_values(x, rest[0], eps), rest[1:]
        return fn(x, *rest)
    return (jax.checkpoint(run) if recompute else run), \
        ((pre_norm.weight,) if pre_norm is not None else ())


def post_normed(fn, post_norm, recompute):
    """`fn(x, *rest)` with the block's RMSNorm BEHIND it (the OLMo 2/3
    block: `x + RMSNorm(f(x))`), and the whole re-run in the backward pass
    where `recompute` says so. -> (the function, the operands to put behind
    x)."""
    if post_norm is None:
        return (jax.checkpoint(fn) if recompute else fn), ()
    eps = post_norm._epsilon

    def run(x, scale, *rest):
        return rms_norm_values(fn(x, *rest), scale, eps)
    return (jax.checkpoint(run) if recompute else run), (post_norm.weight,)


def _held(num_heads, heads_held):
    """`heads_held = (first, count)` of a layer's `num_heads` -> the count
    (all of them where it is None)."""
    first, count = heads_held or (0, num_heads)
    if not (0 <= first and 0 < count and first + count <= num_heads):
        raise ValueError('heads_held %r is no range of %d heads'
                         % (heads_held, num_heads))
    return count


def doc_starts(seg):
    """(B, T) document numbers of a packed row -> for each position, the
    first position of its document."""
    pos = jnp.arange(seg.shape[1], dtype=jnp.int32)[None, :]
    first = jnp.concatenate([jnp.ones_like(seg[:, :1], bool),
                             seg[:, 1:] != seg[:, :-1]], axis=1)
    return jax.lax.cummax(jnp.where(first, pos, 0), axis=1)


class KimiDeltaAttention(Layer):
    """q, k = l2norm(silu(conv(x W))), v = silu(conv(x W_v)) per head
    (`kernels.short_conv`: one kernel pass each on the TPU); a log-decay
    per channel g = -exp(A_log) softplus(x W_a1 W_a2 + dt_bias);
    beta = sigmoid(x W_beta) per head; the delta rule chunk-wise
    (`kernels.delta_rule`: the Pallas kernels on the TPU, off it the XLA
    form of `functional.delta_rule`); W_o [RMSNorm_head(o) * sigmoid(x W_g1 W_g2)].
    """

    def __init__(self, hidden_size, num_heads, head_dim, conv_kernel=4,
                 gate_rank=None, epsilon=1e-5, chunk=64,
                 initializer_range=0.02):
        super().__init__()
        self.num_heads, self.head_dim = num_heads, head_dim
        self.epsilon, self.chunk = epsilon, chunk
        inner = num_heads * head_dim
        rank = gate_rank or head_dim

        def weight(*shape):
            return self.create_parameter(list(shape), attr=ParamAttr(
                initializer=Normal(0., initializer_range)))
        self.q_proj, self.k_proj, self.v_proj = (
            weight(hidden_size, inner) for _ in range(3))
        self.q_conv, self.k_conv, self.v_conv = (
            weight(conv_kernel, inner) for _ in range(3))
        self.decay_a, self.decay_b = weight(hidden_size, rank), \
            weight(rank, inner)
        self.A_log = self.create_parameter(
            [num_heads], default_initializer=Constant(0.0))
        self.dt_bias = self.create_parameter(
            [inner], default_initializer=Constant(0.0))
        self.beta_proj = weight(hidden_size, num_heads)
        self.gate_a, self.gate_b = weight(hidden_size, rank), \
            weight(rank, inner)
        self.o_norm = self.create_parameter(
            [head_dim], default_initializer=Constant(1.0))
        self.o_proj = weight(inner, hidden_size)

    def forward(self, x, segment_ids, pre_norm=None, recompute=False):
        """`pre_norm`: the block's `nn.RMSNorm`, applied to x first and
        inside whatever is recomputed. Rows are always taken one at a time
        and recomputed in the backward pass (below); `recompute` does the
        same for a single row."""
        H, D, eps, chunk = (self.num_heads, self.head_dim, self.epsilon,
                            self.chunk)
        dtype = compute_dtype()
        norm_eps = pre_norm._epsilon if pre_norm is not None else None

        def fn(x, seg, wq, wk, wv, cq, ck, cv, da, db, a_log, dt_bias, wb,
               ga, gb, norm, wo, *pre):
            f32 = jnp.float32

            def rows(x, seg):
                B, T, _ = x.shape
                if pre:
                    x = rms_norm_values(x, pre[0], norm_eps)
                with jax.named_scope('kda.proj'):
                    def short(w, c, head_dim=None):
                        return short_conv(_mm(x, w, dtype), c, seg,
                                          head_dim).reshape(B, T, H, D)
                    q, k, v = short(wq, cq, D), short(wk, ck, D), \
                        short(wv, cv)
                    raw = _mm(_mm(x, da, dtype), db, dtype).astype(f32) \
                        + dt_bias
                    g = -jnp.exp(a_log.astype(f32))[:, None] \
                        * jax.nn.softplus(raw).reshape(B, T, H, D)
                    beta = jax.nn.sigmoid(_mm(x, wb, dtype).astype(f32))
                    gate = jax.nn.sigmoid(
                        _mm(_mm(x, ga, dtype), gb, dtype).astype(f32))
                with jax.named_scope('kda.scan'):
                    o = delta_rule(q, k, v, g, beta, seg, D ** -0.5,
                                   chunk=min(chunk, T),
                                   sub=min(16, chunk, T), dtype=dtype)
                with jax.named_scope('kda.proj'):
                    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                                          + eps) * norm
                    return _mm(o.reshape(B, T, H * D) * gate, wo, dtype)

            if x.shape[0] == 1:
                return (jax.checkpoint(rows) if recompute else rows)(x, seg)
            # a row at a time, recomputed in the backward pass: the layer
            # keeps two dozen arrays the size of q in float32 (the chunked
            # form's factors, the convolutions' and the norms' inputs), and
            # so holds them for one row only
            one = jax.checkpoint(lambda xs: rows(xs[0][None], xs[1][None])[0])
            return jax.lax.map(one, (x, seg))

        return apply_op(fn, (x, segment_ids, self.q_proj, self.k_proj,
                             self.v_proj, self.q_conv, self.k_conv,
                             self.v_conv, self.decay_a, self.decay_b,
                             self.A_log, self.dt_bias, self.beta_proj,
                             self.gate_a, self.gate_b, self.o_norm,
                             self.o_proj)
                        + ((pre_norm.weight,) if pre_norm is not None else ()))


class GroupedQueryAttention(Layer):
    """Causal attention of `num_heads` query heads over `num_kv_heads` key /
    value heads of `head_dim` (query head h reads K/V head h // group), no
    bias: q = x W_q, k = x W_k, v = x W_v; q and k rotated in the half-split
    form (`rotate_halves`, under the scope `attn.rope`; on the TPU one pass
    of `kernels/rotary.py`, which also writes the flash kernels' layout) by
    a position that restarts at each document of a packed row;
    softmax(q k^T / sqrt(head_dim)) over the keys of the query's document,
    the last `window` of them where a window is given; W_o.

    The rotary table is DATA: `inv_freq` and `rope_factor`, which multiplies
    cos and sin (YaRN's attention factor; the scores take its square). A
    plain table and a YaRN table are one code path. The table has
    `rotary_dim` / 2 rates (None: `head_dim`, the whole head turns); with a
    smaller `rotary_dim` the head's first `rotary_dim` channels turn, in the
    half-split form inside them, and the rest pass. With `inv_freq=None`
    nothing is rotated and no `attn.rope` scope is entered (a decoder whose
    state-space layers carry the order).

    `gate='per_head'`: one scalar a query head and token on the heads' way
    into W_o, o_h <- sigmoid(x W_g)_h o_h with `g_proj` (hidden, heads) and
    x the layer's (normed) input, the sigmoid in float32, under the scope
    `attn.gate` (the head-wise form of arXiv:2505.06708); None: no gate, no
    parameter, no scope.

    The layer runs under the scope `attn.window` with a window, else
    `attn.full`; k and v go to the flash kernels at their own head count."""

    def __init__(self, hidden_size, num_heads, num_kv_heads, head_dim,
                 inv_freq, rope_factor=1.0, window=None,
                 initializer_range=0.02, rotary_dim=None, gate=None):
        super().__init__()
        if num_heads % num_kv_heads:
            raise ValueError('%d query heads do not group over %d K/V heads'
                             % (num_heads, num_kv_heads))
        if gate not in (None, 'per_head'):
            raise ValueError('no output gate %r' % (gate,))
        self.heads = (num_heads, num_kv_heads, head_dim)
        self.inv_freq = None if inv_freq is None \
            else np.asarray(inv_freq, np.float32)
        turned = head_dim if rotary_dim is None else rotary_dim
        if inv_freq is not None and (self.inv_freq.shape != (turned // 2,)
                                     or not 0 < turned <= head_dim):
            raise ValueError('inv_freq %r is no table of %d channels of a '
                             '%d-wide head' % (self.inv_freq.shape, turned,
                                               head_dim))
        self.rope_factor, self.window = float(rope_factor), window

        def weight(*shape):
            return self.create_parameter(list(shape), attr=ParamAttr(
                initializer=Normal(0., initializer_range)))
        self.q_proj = weight(hidden_size, num_heads * head_dim)
        self.k_proj = weight(hidden_size, num_kv_heads * head_dim)
        self.v_proj = weight(hidden_size, num_kv_heads * head_dim)
        self.o_proj = weight(num_heads * head_dim, hidden_size)
        self.g_proj = weight(hidden_size, num_heads) if gate else None

    def forward(self, x, segment_ids, pre_norm=None, recompute=False):
        H, HK, D = self.heads
        inv_freq, factor, window = self.inv_freq, self.rope_factor, \
            self.window
        gated = self.g_proj is not None
        dtype = compute_dtype()

        def fn(x, seg, wq, wk, wv, wo, *wg):
            from ...kernels.flash_attention import flash_attention_bhld
            B, T, _ = x.shape
            with jax.named_scope('attn.full' if window is None
                                 else 'attn.window'):
                q = _mm(x, wq, dtype).reshape(B, T, H, D)
                k = _mm(x, wk, dtype).reshape(B, T, HK, D)
                v = _mm(x, wv, dtype).reshape(B, T, HK, D)
                start = doc_starts(seg)
                if inv_freq is not None:
                    with jax.named_scope('attn.rope'):
                        at = jnp.arange(T, dtype=jnp.int32)[None, :] - start
                        q, k = (rotary_halves(t, at, inv_freq, factor)
                                for t in (q, k))
                else:
                    q, k = jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2)
                v = jnp.swapaxes(v, 1, 2)
                o = flash_attention_bhld(q, k, v, causal=True,
                                         doc_start=start, window=window)
                o = jnp.swapaxes(o, 1, 2)
                if gated:
                    with jax.named_scope('attn.gate'):
                        xx, ww = (x, wg[0]) if dtype is None else (
                            x.astype(dtype), wg[0].astype(dtype))
                        g = jax.nn.sigmoid(jnp.matmul(
                            xx, ww, preferred_element_type=jnp.float32))
                        o = (o * g[..., None]).astype(o.dtype)
                return _mm(o.reshape(B, T, H * D), wo, dtype)

        run, front = pre_normed(fn, pre_norm, recompute)
        return apply_op(run, (x,) + front + (
            segment_ids, self.q_proj, self.k_proj, self.v_proj, self.o_proj)
            + ((self.g_proj,) if gated else ()))


class LatentAttention(Layer):
    """Multi-head latent attention: q = x W_q, or with `q_lora_rank` the
    low-rank q = RMSNorm(x W_qa) W_qb; [c, k_pe] = x W_kva, c = RMSNorm(c);
    [k_nope, v] = c W_kvb per head; k = [k_nope, k_pe shared by the heads];
    causal softmax(q k^T / sqrt(d_qk)) inside documents; W_o. q and k are
    wider than v: the flash-attention kernels read both sizes off the shapes.

    No position encoding unless `rope_theta` is given: then the last
    `qk_rope_head_dim` of every head's query and the one shared `k_pe` are
    rotated (`rotate_pairs`, under the scope `mla.rope`, before `k_pe` is
    broadcast to the heads; the query on the TPU in one pass of
    `kernels/rotary.py`, which also writes the flash kernels' layout) by a
    position that restarts at each document of a packed row. A score depends
    on the two positions' difference alone, so it equals the one global
    positions give, at smaller angles
    (docs/EXPERT_LAYER.md, "Rotary positions in packed rows")."""

    def __init__(self, hidden_size, num_heads, qk_nope_head_dim,
                 qk_rope_head_dim, v_head_dim, kv_lora_rank, epsilon=1e-5,
                 initializer_range=0.02, q_lora_rank=None, rope_theta=None):
        super().__init__()
        self.num_heads = num_heads
        self.dims = (qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                     kv_lora_rank)
        self.epsilon, self.rope_theta = epsilon, rope_theta
        self.q_lora_rank = q_lora_rank

        def weight(*shape):
            return self.create_parameter(list(shape), attr=ParamAttr(
                initializer=Normal(0., initializer_range)))
        q_width = num_heads * (qk_nope_head_dim + qk_rope_head_dim)
        if q_lora_rank is None:
            self.q_proj = weight(hidden_size, q_width)
        else:
            self.q_a_proj = weight(hidden_size, q_lora_rank)
            self.q_a_norm = self.create_parameter(
                [q_lora_rank], default_initializer=Constant(1.0))
            self.q_b_proj = weight(q_lora_rank, q_width)
        self.kv_a_proj = weight(hidden_size, kv_lora_rank + qk_rope_head_dim)
        self.kv_a_norm = self.create_parameter(
            [kv_lora_rank], default_initializer=Constant(1.0))
        self.kv_b_proj = weight(kv_lora_rank, num_heads
                                * (qk_nope_head_dim + v_head_dim))
        self.o_proj = weight(num_heads * v_head_dim, hidden_size)

    def forward(self, x, segment_ids, pre_norm=None, recompute=False):
        H, eps, theta = self.num_heads, self.epsilon, self.rope_theta
        nope, rope, dv, rank = self.dims
        low_rank = self.q_lora_rank is not None
        dtype = compute_dtype()

        def normed(c, scale):
            c = c.astype(jnp.float32)
            return c * jax.lax.rsqrt(jnp.mean(c * c, -1, keepdims=True)
                                     + eps) * scale

        def fn(x, seg, *weights):
            from ...kernels.flash_attention import flash_attention_bhld
            wq, (wkva, norm, wkvb, wo) = weights[:-4], weights[-4:]
            B, T, _ = x.shape
            with jax.named_scope('mla.attention'):
                if low_rank:
                    q = _mm(normed(_mm(x, wq[0], dtype), wq[1]), wq[2], dtype)
                else:
                    q = _mm(x, wq[0], dtype)
                q = q.reshape(B, T, H, nope + rope)
                kva = _mm(x, wkva, dtype)
                kv = _mm(normed(kva[..., :rank], norm), wkvb,
                         dtype).reshape(B, T, H, nope + dv)
                k_pe = kva[..., None, rank:]
                if theta is not None:
                    with jax.named_scope('mla.rope'):
                        at = jnp.arange(T, dtype=jnp.int32)[None, :] \
                            - doc_starts(seg)
                        q = rotary_pairs(q, at, theta, rope)
                        k_pe = rotate_pairs(k_pe, at, theta)
                k_pe = jnp.broadcast_to(k_pe, (B, T, H, rope))
                k = jnp.concatenate([kv[..., :nope], k_pe.astype(kv.dtype)],
                                    axis=-1)
                v = kv[..., nope:]
                if theta is None:
                    q = jnp.swapaxes(q, 1, 2)
                k, v = jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)
                o = flash_attention_bhld(
                    q, k, v, causal=True,
                    scale=1.0 / math.sqrt(nope + rope),
                    doc_start=doc_starts(seg))
                return _mm(jnp.swapaxes(o, 1, 2).reshape(B, T, H * dv), wo,
                           dtype)

        q_weights = (self.q_a_proj, self.q_a_norm, self.q_b_proj) \
            if low_rank else (self.q_proj,)
        run, front = pre_normed(fn, pre_norm, recompute)
        return apply_op(run, (x,) + front + (segment_ids,) + q_weights + (
            self.kv_a_proj, self.kv_a_norm, self.kv_b_proj, self.o_proj))


class GatedDeltaNet(Layer):
    """Gated DeltaNet (arXiv:2412.06464), keys of `key_dim` and values of
    `value_dim` a head: q, k = l2norm(silu(conv(x W))), v = silu(conv(x W_v))
    (`kernels.short_conv`); one log-decay a head and token,
    g = -exp(A_log) softplus(x W_a + dt_bias); beta = sigmoid(x W_b), doubled
    where `allow_neg_eigval` (the transition then has eigenvalues down to
    -1); the delta rule (`kernels.delta_rule`) at scale key_dim^-0.5;
    W_o [RMSNorm_head(o) * silu(x W_g)].

    `heads_held = (first, count)`: the layer holds those heads' columns of
    every projection, their taps, `A_log` and `dt_bias`, and their rows of
    W_o; what it returns is their addend of the layer's result
    (docs/HEAD_SHARE.md). No head reads another, so a share needs nothing of
    the heads it lacks."""

    def __init__(self, hidden_size, num_heads, key_dim, value_dim,
                 conv_kernel=4, allow_neg_eigval=False, heads_held=None,
                 epsilon=1e-6, chunk=64, initializer_range=0.02):
        super().__init__()
        self.num_heads, self.heads_held = num_heads, heads_held
        self.heads = H = _held(num_heads, heads_held)
        self.key_dim, self.value_dim = key_dim, value_dim
        self.allow_neg_eigval = allow_neg_eigval
        self.epsilon, self.chunk = epsilon, chunk

        def weight(*shape):
            return self.create_parameter(list(shape), attr=ParamAttr(
                initializer=Normal(0., initializer_range)))
        self.q_proj, self.k_proj = (weight(hidden_size, H * key_dim)
                                    for _ in range(2))
        self.v_proj = weight(hidden_size, H * value_dim)
        self.q_conv, self.k_conv = (weight(conv_kernel, H * key_dim)
                                    for _ in range(2))
        self.v_conv = weight(conv_kernel, H * value_dim)
        self.a_proj, self.b_proj = weight(hidden_size, H), \
            weight(hidden_size, H)
        self.A_log = self.create_parameter(
            [H], default_initializer=Constant(0.0))
        self.dt_bias = self.create_parameter(
            [H], default_initializer=Constant(0.0))
        self.g_proj = weight(hidden_size, H * value_dim)
        self.o_norm = self.create_parameter(
            [value_dim], default_initializer=Constant(1.0))
        self.o_proj = weight(H * value_dim, hidden_size)

    def forward(self, x, segment_ids, post_norm=None, recompute=False):
        """`post_norm`: the block's `nn.RMSNorm`, applied to the result
        inside whatever is recomputed. More rows than one are taken one at a
        time and recomputed in the backward pass, as in
        `KimiDeltaAttention`."""
        H, K, V = self.heads, self.key_dim, self.value_dim
        eps, chunk = self.epsilon, self.chunk
        top = 2.0 if self.allow_neg_eigval else 1.0
        dtype = compute_dtype()

        def fn(x, seg, wq, wk, wv, cq, ck, cv, wa, wb, a_log, dt_bias, wg,
               norm, wo):
            f32 = jnp.float32

            def rows(x, seg):
                B, T, _ = x.shape
                with jax.named_scope('gdn.proj'):
                    q = short_conv(_mm(x, wq, dtype), cq, seg, K)
                    k = short_conv(_mm(x, wk, dtype), ck, seg, K)
                    v = short_conv(_mm(x, wv, dtype), cv, seg, V, norm=False)
                    g = -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(
                        _mm(x, wa, dtype).astype(f32) + dt_bias)
                    beta = top * jax.nn.sigmoid(_mm(x, wb, dtype).astype(f32))
                    gate = jax.nn.silu(_mm(x, wg, dtype).astype(f32))
                with jax.named_scope('gdn.scan'):
                    o = delta_rule(q.reshape(B, T, H, K),
                                   k.reshape(B, T, H, K),
                                   v.reshape(B, T, H, V), g, beta, seg,
                                   K ** -0.5, chunk=min(chunk, T),
                                   sub=min(16, chunk, T), dtype=dtype)
                with jax.named_scope('gdn.proj'):
                    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                                          + eps) * norm
                    return _mm(o.reshape(B, T, H * V) * gate, wo, dtype)

            if x.shape[0] == 1:
                return rows(x, seg)
            one = jax.checkpoint(lambda xs: rows(xs[0][None], xs[1][None])[0])
            return jax.lax.map(one, (x, seg))

        # (more rows than one are recomputed row by row inside `fn`: a
        # checkpoint around that would run the forward a third time)
        run, behind = post_normed(fn, post_norm,
                                  recompute and x.shape[0] == 1)
        return apply_op(run, (x,) + behind + (
            segment_ids, self.q_proj, self.k_proj, self.v_proj, self.q_conv,
            self.k_conv, self.v_conv, self.a_proj, self.b_proj, self.A_log,
            self.dt_bias, self.g_proj, self.o_norm, self.o_proj))


class CausalSelfAttention(Layer):
    """Multi-head causal attention as the OLMo 2/3 block has it: q =
    RMSNorm(x W_q), k = RMSNorm(x W_k), each norm over the WHOLE projection
    (every head's channels together), v = x W_v, no bias, no rotation;
    softmax(q k^T / sqrt(head_dim)) inside documents (`attention_blhd`: the
    flash kernels with `doc_start` on the TPU); W_o.

    `heads_held = (first, count)`: the layer holds those heads' columns of
    W_q, W_k, W_v and of the two norms' scales and their rows of W_o, and
    returns their addend of the layer's result. The one number a share
    lacks is the two norms' mean square over the heads it does not hold:
    `forward` takes it as `qk_mean_square` (what the chips of a group would
    add up; `qk_mean_square(x)` gives a share's own) and norms by the held
    heads' where none is given (docs/HEAD_SHARE.md)."""

    def __init__(self, hidden_size, num_heads, head_dim, heads_held=None,
                 epsilon=1e-6, initializer_range=0.02):
        super().__init__()
        self.num_heads, self.heads_held = num_heads, heads_held
        self.heads = H = _held(num_heads, heads_held)
        self.head_dim, self.epsilon = head_dim, epsilon

        def weight(*shape):
            return self.create_parameter(list(shape), attr=ParamAttr(
                initializer=Normal(0., initializer_range)))
        self.q_proj, self.k_proj, self.v_proj = (
            weight(hidden_size, H * head_dim) for _ in range(3))
        self.q_norm, self.k_norm = (self.create_parameter(
            [H * head_dim], default_initializer=Constant(1.0))
            for _ in range(2))
        self.o_proj = weight(H * head_dim, hidden_size)

    def qk_mean_square(self, x):
        """-> the mean squares (B, T, 1), float32, of this share's query and
        key projections: what a group's chips average for the two norms."""
        dtype = compute_dtype()

        def fn(x, w):
            return jnp.mean(jnp.square(_mm(x, w, dtype).astype(jnp.float32)),
                            -1, keepdims=True)
        return tuple(apply_op(fn, (x, w)) for w in (self.q_proj, self.k_proj))

    def forward(self, x, segment_ids, post_norm=None, recompute=False,
                qk_mean_square=None):
        H, D, eps = self.heads, self.head_dim, self.epsilon
        dtype = compute_dtype()

        def fn(x, seg, wq, wk, wv, nq, nk, wo, ms_q=None, ms_k=None):
            B, T, _ = x.shape
            with jax.named_scope('attn.full'):
                q = rms_norm_values(_mm(x, wq, dtype), nq, eps, ms_q)
                k = rms_norm_values(_mm(x, wk, dtype), nk, eps, ms_k)
                if dtype is not None:
                    q, k = q.astype(dtype), k.astype(dtype)
                q, k, v = (t.reshape(B, T, H, D)
                           for t in (q, k, _mm(x, wv, dtype)))
                o = attention_blhd(q, k, v, causal=True,
                                   doc_start=doc_starts(seg))
                return _mm(o.reshape(B, T, H * D), wo, dtype)

        run, behind = post_normed(fn, post_norm, recompute)
        return apply_op(run, (x,) + behind + (
            segment_ids, self.q_proj, self.k_proj, self.v_proj, self.q_norm,
            self.k_norm, self.o_proj) + tuple(qk_mean_square or ()))
