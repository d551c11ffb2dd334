"""Attention functionals.

Parity: python/paddle/nn/layer/transformer.py core compute. TPU-first:
kernels/flash_attention.py computes it, and decides there between the Pallas
flash kernels and one fused softmax(QK^T/sqrt(d))V expression XLA can fuse.
"""
from ...core import rng as _rng
from ...core.tensor import apply_op
from ...tensor._helpers import _t

__all__ = ['scaled_dot_product_attention', 'multi_head_attention']


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False, training=True,
                                 name=None):
    """query/key/value: (B, L, H, D) paddle-style. Returns (B, L, H, D)."""
    from ...kernels.flash_attention import attention_blhd
    tensors = [_t(query), _t(key), _t(value)]
    if attn_mask is not None:
        tensors.append(_t(attn_mask))
    p_eff = float(dropout_p) if training else 0.0
    drop_key = _rng.next_key() if p_eff > 0.0 else None

    def fn(q, k, v, *mask):
        return attention_blhd(q, k, v, mask[0] if mask else None, is_causal,
                              p_eff, drop_key)
    return apply_op(fn, tuple(tensors))


def multi_head_attention(query, key, value, num_heads, wq, wk, wv, wo,
                         bq=None, bk=None, bv=None, bo=None, attn_mask=None,
                         dropout_p=0.0, is_causal=False, cache=None,
                         training=True):
    """Functional MHA on (B, L, E) with (E, E) projection weights."""
    from .common import linear, dropout as _dropout
    q = linear(query, wq, bq)
    k = linear(key, wk, bk)
    v = linear(value, wv, bv)
    B, Lq, E = q.shape
    hd = E // num_heads
    q = q.reshape([B, Lq, num_heads, hd])
    k = k.reshape([B, k.shape[1], num_heads, hd])
    v = v.reshape([B, v.shape[1], num_heads, hd])
    if cache is not None:
        k = cache.append_k(k)
        v = cache.append_v(v)
    out = scaled_dot_product_attention(q, k, v, attn_mask=attn_mask,
                                       dropout_p=dropout_p, is_causal=is_causal,
                                       training=training)
    out = out.reshape([B, Lq, E])
    return linear(out, wo, bo)
