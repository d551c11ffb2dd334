"""Flash attention: Pallas TPU kernels, forward AND backward, with dropout.

Replaces the reference's fused attention CUDA path
(paddle/fluid/operators/fused/*attention*). Online-softmax tiling keeps the
(L, L) score matrix out of HBM in both directions: the forward streams K/V
tiles against resident Q tiles and saves only O and the per-row logsumexp;
the backward is ONE kernel (grid over K tiles, loop over Q tiles): per tile
pair it recomputes the probabilities from (q, k, lse), the dropout mask and
dP once and takes dV, dK and dQ from them, five matmuls for the four the
mathematics needs plus the recomputed scores. delta = rowsum(dO * O) is made
inside it from O. With one K tile (every key-padding call) a Q tile's dQ is
whole inside the instance and written straight out; otherwise it sums over the
grid's K axis in an fp32 VMEM scratch and is cast at the last K tile. No
(L, L) matrix is ever materialized.

Features:
- causal and non-causal attention;
- additive key-padding bias of shape (B, Lk) — the form BERT's (B, 1, 1, L)
  padding mask reduces to;
- a value head size that differs from the query/key head size (latent
  attention trains with 192-wide q/k and 128-wide v): read off the shapes;
- grouped-query heads: k and v may hold fewer heads than q (a divisor of
  its count), read off the shapes; query head h reads K/V head h // group
  through the block index, so no K or V at the query head count exists. The
  backward's grid then runs (batch x K/V heads, group, K tiles) and keeps a
  K/V head's whole dK and dV in VMEM, in float32, over its group: the
  group's sum is made there (docs/ATTENTION.md). No bias, no dropout there;
- an attention window on packed rows: ``window`` keys a query sees at most,
  itself among them. It enters through the per-row first key alone
  (``row_starts``: the later of the document's start and position - window
  + 1), so masks, tile bounds and tile counts hold for it as for documents;
- packed documents under the causal mask: ``doc_start`` (B, L) names, for
  each query position, the first position of its document; a query sees
  the keys from there to itself. It buys more than the mask: the tile
  loops take their bounds from it (``doc_tile_bounds``, two small int32
  arrays made in XLA once a call and read by the kernels from SMEM). The
  forward starts a Q tile's sweep at the first K tile any of its rows can
  see, the backward ends a K tile's sweep after the last Q tile that can
  see it, so a tile pair wholly inside other documents is never visited
  (on rows of 8192 packed from documents of median 1024: 45% of the pairs
  under the diagonal are). Such a pair contributes exact zeros, so the
  results are those of the full sweep element for element. A call without
  ``doc_start`` keeps the constant bounds and traces to the same program
  as before;
- attention-probability dropout INSIDE the kernel: the keep-mask for tile
  (bh, q_block, k_block) is regenerated from the TPU hardware PRNG
  (pltpu.prng_seed keyed on the tile coordinates) identically in the forward
  and the backward kernel, so no (L, L) mask is stored.

The non-dropout kernels accept interpret=True so their numerics are testable
on the CPU backend (tests/test_flash_attention.py); the interpret emulation of
prng_random_bits is a zero-stub, so the dropout path is validated on real TPU
hardware (tests marked tpu-only in tests/test_flash_attention.py; on the
chip, ``checks.check_flash_dropout_backward`` holds the dropout-on backward
to finite differences of its forward).

Two entries, and each states its own rule for taking the kernels:
``flash_attention_bhld`` for a layer that hands over (B, H, L, D) operands
(latent attention; the chip's checks) takes them wherever they run and the
sequence tiles, and ``attention_blhd`` for ``nn.functional``'s
(B, L, H, D) call with a free-form mask adds what that call needs: a mask
the kernels can express and a sequence long enough for them to win. Off
their rule each takes plain-XLA attention with identical semantics (dropout
there uses jax.random — same distribution, different stream, and in a step
sharded over a mesh each device's own draw: ``_common.keep_mask``); which
path a trace took is marked in the HLO (``_common.took``).
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import (keep_mask, pallas_runs, spmd_kernel,
                      tile_keep_scale as _tile_keep_scale, took)

NEG_INF = -1e30
_BLOCK = 512        # the default tile, along queries and along keys
# nn.functional's attention under this length stays XLA's own fusion of the
# scores: the benchmark's seq128 and seq512 cells stand on either side of it
_MIN_SEQ = 512
LSE_EMPTY = 1e30  # lse sentinel for fully-masked rows: exp(s - BIG) == 0


def _attn_reference(q, k, v, causal, scale, kpad_bias=None, dropout_p=0.0,
                    dropout_key=None, doc_start=None):
    """Plain XLA attention on (B, H, L, D) — fallback + ground truth.

    kpad_bias: optional (B, Lk) additive bias (0 for keep, large negative for
    masked keys). doc_start: optional (B, L) first key each query sees
    (``row_starts``). k and v may hold fewer heads than q: each is then
    repeated over its group of query heads (here, off the kernels' path,
    only).
    """
    group = q.shape[1] // k.shape[1]
    if group > 1:
        k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    scores = jnp.einsum('bhld,bhmd->bhlm', q, k) * scale
    if kpad_bias is not None:
        scores = scores + kpad_bias[:, None, None, :].astype(scores.dtype)
    if doc_start is not None:
        cols = jnp.arange(scores.shape[-1], dtype=jnp.int32)
        scores = jnp.where(cols >= doc_start[:, None, :, None], scores,
                           NEG_INF)
    if causal:
        L, M = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((L, M), dtype=bool))
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    if dropout_p > 0.0 and dropout_key is not None:
        keep = keep_mask(dropout_key, 1.0 - dropout_p, probs.shape,
                         _BHLD[:2] + (None, None))
        probs = jnp.where(keep, probs / (1.0 - dropout_p),
                          jnp.zeros_like(probs))
    return jnp.einsum('bhlm,bhmd->bhld', probs, v)


def _score_tile(q, k_tile, bias_tile, causal, q_offset, k_offset, scale,
                start=None):
    """(block_q, block_k) scores for one tile pair, masked.

    q/k stay in their native dtype (bf16 on the training path) so the MXU
    runs native-bf16 with fp32 accumulation — upcasting the tiles first would
    force fp32 MXU passes at a fraction of the throughput. The scale is
    applied to the fp32 scores after the matmul.
    """
    s = jnp.dot(q, k_tile.T, preferred_element_type=jnp.float32) * scale
    if bias_tile is not None:
        s = s + bias_tile
    if causal:
        bq, bk = s.shape
        rows = q_offset + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = k_offset + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(rows >= cols, s, NEG_INF)
    if start is not None:       # (block_q, 1): where each row's document begins
        cols = k_offset + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols >= start, s, NEG_INF)
    return s


def _global_bh(seed_ref, heads):
    """This grid row's (batch, head) index in the WHOLE (B, H) array.
    seed_ref is (1, 3) int32 [seed, first batch, first head] of this
    device's shard (``_seed_and_shard``); heads = (local, total) head
    counts. Dropout tiles are keyed on it, so the mask does not depend on
    how batch and heads were partitioned."""
    h_local, h_total = heads
    bh = pl.program_id(0)
    return ((seed_ref[0, 1] + bh // h_local) * h_total
            + seed_ref[0, 2] + bh % h_local)


def _tile_bound(bound_ref, heads, axis=1):
    """This grid point's loop bound (``doc_tile_bounds``): bound_ref is the
    (batch rows x tiles,) int32 array of this device's shard in SMEM, the
    grid (batch x heads, ..., tiles) with the tiles along `axis`."""
    return bound_ref[(pl.program_id(0) // heads[0]) * pl.num_programs(axis)
                     + pl.program_id(axis)]


def _seed_and_shard(seed, shard):
    return jnp.stack([seed.astype(jnp.int32).reshape(()),
                      shard['b'][0].astype(jnp.int32),
                      shard['h'][0].astype(jnp.int32)]).reshape(1, 3)


_BHLD = ('b', 'h', 'l', 'd')
_ROLES = {'b': 'batch', 'h': 'heads'}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(*refs, block_k, seq_len, causal, scale, has_bias, dropout_p,
                heads, has_doc=False):
    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    idx = 3
    bias_ref = seed_ref = start = None
    if has_bias:
        bias_ref = refs[idx]; idx += 1
    if dropout_p > 0.0:
        seed_ref = refs[idx]; idx += 1
    first = 0
    if has_doc:
        start = refs[idx][0]; idx += 1                 # (block_q, 1) int32
        # the first K tile a row of this Q tile can see
        first = _tile_bound(refs[idx], heads); idx += 1
    o_ref, lse_ref = refs[idx:idx + 2]

    q = q_ref[0]                                       # (block_q, d) native
    block_q = q.shape[0]
    q_blk = pl.program_id(1)
    q_offset = q_blk * block_q

    m = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((block_q, 1), jnp.float32)
    acc = jnp.zeros((block_q, v_ref.shape[2]), jnp.float32)

    if causal:
        n_blocks = (q_offset + block_q + block_k - 1) // block_k
        # tiles strictly below the diagonal need no causal mask: the mask's
        # iota/where per tile costs real VPU time, so split the sweep into an
        # unmasked interior phase and a masked diagonal phase. The numerator
        # is clamped non-negative BEFORE the divide: Mosaic lowers // as
        # truncating division, which disagrees with floor on negatives.
        n_full = jnp.maximum(q_offset + 1 - block_k, 0) // block_k
        n_full = jnp.where(q_offset + 1 >= block_k, n_full + 1, 0)
    else:
        n_blocks = seq_len // block_k
        n_full = n_blocks

    def make_body(masked):
        def body(i, carry):
            m_i, l_i, acc_i = carry
            k_tile = k_ref[0, pl.dslice(i * block_k, block_k), :]
            v_tile = v_ref[0, pl.dslice(i * block_k, block_k), :]
            bias_tile = None
            if bias_ref is not None:
                bias_tile = bias_ref[0, :, pl.dslice(i * block_k, block_k)
                                     ].astype(jnp.float32)  # (1, block_k)
            s = _score_tile(q, k_tile, bias_tile, masked, q_offset,
                            i * block_k, scale, start)
            m_new = jnp.maximum(m_i, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_i - m_new)
            # l accumulates UNdropped p: dropout applies to the normalized
            # probs; the final o = acc / l realizes drop(softmax(s)) @ v.
            l_new = l_i * corr + jnp.sum(p, axis=-1, keepdims=True)
            p_acc = p
            if dropout_p > 0.0:
                nq, nk = seq_len // block_q, seq_len // block_k
                tile_id = (_global_bh(seed_ref, heads) * nq + q_blk) * nk + i
                p_acc = p * _tile_keep_scale(seed_ref, tile_id, p.shape,
                                             dropout_p)
            # p in the value matmul rides the MXU in v's dtype (bf16 on the
            # training path); the accumulator stays fp32
            acc_new = acc_i * corr + jnp.dot(
                p_acc.astype(v_tile.dtype), v_tile,
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc_new
        return body

    m, l, acc = jax.lax.fori_loop(first, n_full, make_body(False),
                                  (m, l, acc))
    if causal:
        m, l, acc = jax.lax.fori_loop(
            jnp.maximum(n_full, first) if has_doc else n_full, n_blocks,
            make_body(True), (m, l, acc))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), LSE_EMPTY)
    lse_ref[0] = lse.astype(jnp.float32)                # (block_q, 1)


def _fwd_vmem_limit(L, d, dv, bq, itemsize, row_operands):
    """The forward kernel keeps a head's whole K and V in VMEM
    (double-buffered, the minor dim padded to 128 lanes) beside its Q-tile
    blocks; `row_operands` counts its (block_q, 1) blocks (lse, and
    doc_start where there is one). Inside the 16 MiB Mosaic gives a kernel
    unasked the limit is left alone: None (every call with L <= 4096 at head
    size 128)."""
    lanes, lanes_v = -(-d // 128) * 128, -(-dv // 128) * 128
    need = (2 * L * (lanes + lanes_v) * itemsize    # k, v
            + 2 * bq * (lanes + lanes_v) * itemsize  # q in, o out
            + 2 * bq * 128 * 4 * row_operands       # lse, doc_start
            + 4 * bq * bq * 4)                      # live fp32 score tiles
    return None if need <= (14 << 20) else need * 3 // 2


def _flash_forward(q, k, v, kpad_bias, seed, doc, causal, scale,
                   block_q, block_k, dropout_p, interpret):
    """doc: None, or (doc_start, lo, hi) as ``doc_tile_bounds`` makes the
    bounds; the forward reads ``lo``."""
    L, d = q.shape[2:]
    dv = v.shape[3]
    bq, bk = min(block_q, L), min(block_k, L)
    has_bias = kpad_bias is not None
    has_doc = doc is not None
    args, dims = [q, k, v], [_BHLD] * 3
    if has_bias:
        # (B, 1, L) so the block shape (1, 1, L) satisfies TPU tiling rules
        args.append(kpad_bias.astype(jnp.float32)[:, None, :])
        dims.append(('b', None, 'l'))
    if dropout_p > 0.0:
        args.append(seed)
        dims.append((None, None))
    if has_doc:
        args.append(doc[0].astype(jnp.int32)[:, :, None])       # (B, L, 1)
        dims.append(('b', 'l', None))
        args.append(doc[1])                                     # (B, L // bq)
        dims.append(('b', None))
    extra = {'has_doc': True} if has_doc else {}
    limit = _fwd_vmem_limit(L, d, dv, bq, q.dtype.itemsize, 1 + int(has_doc))
    if limit is not None:
        extra_call = {'compiler_params': pltpu.CompilerParams(
            vmem_limit_bytes=limit)}
    else:
        extra_call = {}

    def call(*args, shard):
        b, h = args[0].shape[:2]        # this device's batch and heads
        group = h // args[1].shape[1]   # query heads to a K/V head
        args = [t.reshape((-1, L) + t.shape[3:]) for t in args[:3]
                ] + list(args[3:])
        kernel = functools.partial(
            _fwd_kernel, block_k=bk, seq_len=L, causal=causal, scale=scale,
            has_bias=has_bias, dropout_p=dropout_p,
            heads=(h, shard['h'][1]), **extra)
        if group == 1:
            def kv(bh, i):
                return (bh, 0, 0)
        else:       # the heads of a group follow each other: its K and V
            def kv(bh, i):      # are fetched once for all of them
                return (bh // group, 0, 0)
        in_specs = [
            pl.BlockSpec((1, bq, d), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, L, d), kv),
            pl.BlockSpec((1, L, dv), kv),
        ]
        n_fixed = 3
        if has_bias:
            in_specs.append(
                pl.BlockSpec((1, 1, L), lambda bh, i: (bh // h, 0, 0)))
            n_fixed += 1
        if dropout_p > 0.0:
            in_specs.append(pl.BlockSpec((1, 3), lambda bh, i: (0, 0)))
            args[n_fixed] = _seed_and_shard(args[n_fixed], shard)
        if has_doc:
            in_specs.append(
                pl.BlockSpec((1, bq, 1), lambda bh, i: (bh // h, i, 0)))
            in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
            args[-1] = args[-1].reshape(-1)
        o, lse = pl.pallas_call(
            kernel,
            grid=(b * h, L // bq),
            in_specs=in_specs,
            out_specs=(pl.BlockSpec((1, bq, dv), lambda bh, i: (bh, i, 0)),
                       pl.BlockSpec((1, bq, 1), lambda bh, i: (bh, i, 0))),
            out_shape=(jax.ShapeDtypeStruct((b * h, L, dv), q.dtype),
                       jax.ShapeDtypeStruct((b * h, L, 1), jnp.float32)),
            interpret=interpret, **extra_call,
        )(*args)
        return o.reshape(b, h, L, dv), lse.reshape(b, h, L)

    return spmd_kernel(call, dims, [_BHLD, _BHLD[:3]], _ROLES,
                       scope='flash_attention.pallas')(*args)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_kernel(*refs, block_q, seq_len, causal, scale, has_bias, dropout_p,
                heads, has_doc=False, group=1):
    """dQ, dK and dV of one (head, K tile) from ONE pass over its score
    tiles. Grid (b*h, K tiles); the loop runs over the Q tiles.

    `group` > 1 (grouped-query heads): grid (b*K/V heads, group, K tiles),
    `heads` counting the K/V heads. dK and dV are then the K/V head's WHOLE
    (L, d) float32 blocks, which stay in VMEM over the group and the K tiles
    and take each query head's part of a K tile as it comes."""
    axis = 1 if group == 1 else 2           # the grid's K-tile axis
    refs = list(refs)
    q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref = refs[:6]
    idx = 6
    bias_ref = seed_ref = start_ref = None
    if has_bias:
        bias_ref = refs[idx]; idx += 1
    if dropout_p > 0.0:
        seed_ref = refs[idx]; idx += 1
    if has_doc:
        start_ref = refs[idx]; idx += 1                 # (1, L, 1) int32
        # one past the last Q tile that can see a key of this K tile
        last = _tile_bound(refs[idx], heads, axis); idx += 1
    dq_ref, dk_ref, dv_ref = refs[idx:idx + 3]

    k = k_ref[0]                                        # (block_k, d) native
    v = v_ref[0]
    block_k = k.shape[0]
    nq, nk = seq_len // block_q, seq_len // block_k
    # one K tile (every key-padding call): a Q tile's dQ is whole inside
    # this instance. More: it sums over the grid's K axis, in fp32
    dq_acc = refs[idx + 3] if nk > 1 else None
    k_blk = pl.program_id(axis)
    k_offset = k_blk * block_k
    bias_tile = None
    if bias_ref is not None:
        bias_tile = bias_ref[0].astype(jnp.float32)     # (1, block_k)

    if causal:
        start = k_offset // block_q
        # q tiles whose every row >= every col of this k tile are unmasked:
        # i*block_q >= k_offset + block_k - 1
        start_full = (k_offset + block_k - 1 + block_q - 1) // block_q
    else:
        start = 0
        start_full = 0

    if dq_acc is not None:
        @pl.when(k_blk == 0)
        def _():
            dq_acc[...] = jnp.zeros_like(dq_acc)

    def make_body(masked):
        def body(i, carry):
            dk_acc, dv_acc = carry
            rows = pl.dslice(i * block_q, block_q)
            q_tile = q_ref[0, rows, :]
            do_tile = do_ref[0, rows, :]
            lse = lse_ref[0, rows, :].astype(jnp.float32)   # (block_q, 1)
            delta = jnp.sum(do_tile.astype(jnp.float32)
                            * o_ref[0, rows, :].astype(jnp.float32),
                            axis=-1, keepdims=True)         # (block_q, 1)
            start = None if start_ref is None else start_ref[0, rows, :]
            s = _score_tile(q_tile, k, bias_tile, masked, i * block_q,
                            k_offset, scale, start)
            p = jnp.exp(s - lse)                        # (block_q, block_k)
            p_drop = p
            dp = jnp.dot(do_tile, v.T, preferred_element_type=jnp.float32)
            if dropout_p > 0.0:
                tile_id = (_global_bh(seed_ref, heads) * nq + i) * nk + k_blk
                keep_scale = _tile_keep_scale(seed_ref, tile_id, p.shape,
                                              dropout_p)
                p_drop = p * keep_scale
                dp = dp * keep_scale
            # cast, then transpose: half the bytes through the transpose
            # unit, the same numbers
            dv_acc = dv_acc + jnp.dot(p_drop.astype(do_tile.dtype).T, do_tile,
                                      preferred_element_type=jnp.float32)
            ds = (p * (dp - delta)).astype(q_tile.dtype)
            dk_acc = dk_acc + jnp.dot(ds.T, q_tile,
                                      preferred_element_type=jnp.float32)
            dq = jnp.dot(ds, k, preferred_element_type=jnp.float32)
            if dq_acc is None:
                dq_ref[0, rows, :] = (dq * scale).astype(dq_ref.dtype)
            else:
                dq_acc[rows, :] += dq
            return dk_acc, dv_acc
        return body

    zero = jnp.zeros((block_k, k.shape[1]), jnp.float32)
    if v.shape[1] == k.shape[1]:
        zero_v = zero
    else:
        zero_v = jnp.zeros((block_k, v.shape[1]), jnp.float32)
    if causal:
        bound = jnp.minimum(jnp.maximum(start_full, start), nq)
        dk, dv = jax.lax.fori_loop(start, bound, make_body(True),
                                   (zero, zero_v))
        dk, dv = jax.lax.fori_loop(
            bound, jnp.minimum(last, nq) if has_doc else nq,
            make_body(False), (dk, dv))
    else:
        dk, dv = jax.lax.fori_loop(start, nq, make_body(False),
                                   (zero, zero_v))
    if group == 1:
        dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv.astype(dv_ref.dtype)
    else:
        at = pl.dslice(k_offset, block_k)

        @pl.when(pl.program_id(1) == 0)
        def _():
            dk_ref[0, at, :] = dk * scale
            dv_ref[0, at, :] = dv

        @pl.when(pl.program_id(1) > 0)
        def _():
            dk_ref[0, at, :] += dk * scale
            dv_ref[0, at, :] += dv
    if dq_acc is not None:
        @pl.when(k_blk == nk - 1)
        def _():
            dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _bwd_vmem_limit(L, d, bq, bk, itemsize, dv=None, has_doc=False,
                    grouped=False):
    """The backward kernel keeps a head's whole Q, O, dO, lse and dQ in
    VMEM (double-buffered, the minor dim padded to 128 lanes) beside its
    K-tile blocks and a few (bq, bk) fp32 temporaries. While that fits the
    16 MiB Mosaic gives a kernel unasked (every tiling to L = 1024, most
    to 2048) the limit is left alone: None. Past it, ask for what the
    blocks need and half as much again; the compiler refuses what the chip
    cannot give. `grouped`: dK and dV are whole float32 heads besides."""
    lanes = -(-d // 128) * 128
    lanes_v = lanes if dv is None else -(-dv // 128) * 128
    need = (2 * 2 * L * (lanes + lanes_v) * itemsize    # q, dq; o, do
            + 2 * L * 128 * 4 * (2 if has_doc else 1)   # lse (L, 1), doc_start
            + (L * lanes * 4 if bk < L else 0)  # dq's fp32 scratch
            + 2 * 2 * bk * (lanes + lanes_v) * itemsize     # k, dk; v, dv
            + 2 * bq * bk * 4)                  # two live fp32 score tiles
    if grouped:
        need += 2 * L * (lanes + lanes_v) * 4
    return None if need <= (14 << 20) else need * 3 // 2


def _flash_backward(q, k, v, o, lse, kpad_bias, seed, doc, g, causal,
                    scale, block_q, block_k, dropout_p, interpret):
    """doc: as ``_flash_forward``'s; the backward reads ``hi``."""
    L, d = q.shape[2:]
    dv_ = v.shape[3]
    bq, bk = min(block_q, L), min(block_k, L)
    nk = L // bk
    has_bias = kpad_bias is not None
    has_doc = doc is not None
    args = [q, k, v, o, g, lse[..., None]]
    dims = [_BHLD] * 5 + [('b', 'h', 'l', None)]
    if has_bias:
        args.append(kpad_bias.astype(jnp.float32)[:, None, :])  # (B,1,L)
        dims.append(('b', None, 'l'))
    if dropout_p > 0.0:
        args.append(seed)
        dims.append((None, None))
    if has_doc:
        args.append(doc[0].astype(jnp.int32)[:, :, None])       # (B, L, 1)
        dims.append(('b', 'l', None))
        args.append(doc[2])                                     # (B, L // bk)
        dims.append(('b', None))
    extra = {'has_doc': True} if has_doc else {}

    def call(*args, shard):
        b, h = args[0].shape[:2]        # this device's batch and heads
        if args[1].shape[1] != h:
            return grouped_call(*args)
        args = [t.reshape((b * h,) + t.shape[2:]) for t in args[:6]
                ] + list(args[6:])
        if dropout_p > 0.0:
            at = 6 + int(has_bias)
            args[at] = _seed_and_shard(args[at], shard)
        kernel = functools.partial(
            _bwd_kernel, block_q=bq, seq_len=L, causal=causal, scale=scale,
            has_bias=has_bias, dropout_p=dropout_p,
            heads=(h, shard['h'][1]), **extra)

        full_ld = pl.BlockSpec((1, L, d), lambda bh, j: (bh, 0, 0))
        tile_kd = pl.BlockSpec((1, bk, d), lambda bh, j: (bh, j, 0))
        if dv_ == d:
            full_lv, tile_kv = full_ld, tile_kd
        else:
            full_lv = pl.BlockSpec((1, L, dv_), lambda bh, j: (bh, 0, 0))
            tile_kv = pl.BlockSpec((1, bk, dv_), lambda bh, j: (bh, j, 0))
        in_specs = [full_ld, tile_kd, tile_kv, full_lv, full_lv,
                    pl.BlockSpec((1, L, 1), lambda bh, j: (bh, 0, 0))]
        if has_bias:
            in_specs.append(
                pl.BlockSpec((1, 1, bk), lambda bh, j: (bh // h, 0, j)))
        if dropout_p > 0.0:
            in_specs.append(pl.BlockSpec((1, 3), lambda bh, j: (0, 0)))
        if has_doc:
            in_specs.append(
                pl.BlockSpec((1, L, 1), lambda bh, j: (bh // h, 0, 0)))
            in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
            args[-1] = args[-1].reshape(-1)
        # dQ's block is the head's whole (L, d) at every K tile: it stays in
        # VMEM along that axis (which must therefore run in order) and goes
        # out once
        dq, dk, dv = pl.pallas_call(
            kernel,
            grid=(b * h, nk),
            in_specs=in_specs,
            out_specs=(full_ld, tile_kd, tile_kv),
            out_shape=(jax.ShapeDtypeStruct((b * h, L, d), q.dtype),
                       jax.ShapeDtypeStruct((b * h, L, d), k.dtype),
                       jax.ShapeDtypeStruct((b * h, L, dv_), v.dtype)),
            scratch_shapes=([pltpu.VMEM((L, d), jnp.float32)] if nk > 1
                            else []),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=('parallel', 'arbitrary'),
                vmem_limit_bytes=_bwd_vmem_limit(
                    L, d, bq, bk, q.dtype.itemsize, dv_, has_doc)),
            interpret=interpret,
        )(*args)
        return (dq.reshape(b, h, L, d), dk.reshape(b, h, L, d),
                dv.reshape(b, h, L, dv_))

    def grouped_call(*args):
        """Grouped-query heads (no bias, no dropout: the entry refuses
        them): grid (batch x K/V heads, group, K tiles). A query head's Q, O,
        dO, lse and dQ stay in VMEM over its K tiles as above; the K/V
        head's dK and dV, whole and in float32, stay over the group too and
        go out once, so the group's sum is made in VMEM and no per-query-head
        dK or dV exists."""
        b, h, hk = args[0].shape[0], args[0].shape[1], args[1].shape[1]
        group = h // hk
        args = [t.reshape((-1,) + t.shape[2:]) for t in args[:6]
                ] + list(args[6:])
        kernel = functools.partial(
            _bwd_kernel, block_q=bq, seq_len=L, causal=causal, scale=scale,
            has_bias=False, dropout_p=0.0, heads=(hk, hk), group=group,
            **extra)

        def head(n, g, j):
            return (n * group + g, 0, 0)

        def whole(width):
            return pl.BlockSpec((1, L, width), head)

        def tile(width):
            return pl.BlockSpec((1, bk, width), lambda n, g, j: (n, j, 0))

        def summed(width):
            return pl.BlockSpec((1, L, width), lambda n, g, j: (n, 0, 0))
        in_specs = [whole(d), tile(d), tile(dv_), whole(dv_), whole(dv_),
                    whole(1)]
        if has_doc:
            in_specs.append(pl.BlockSpec(
                (1, L, 1), lambda n, g, j: (n // hk, 0, 0)))
            in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
            args[-1] = args[-1].reshape(-1)
        dq, dk, dv = pl.pallas_call(
            kernel,
            grid=(b * hk, group, nk),
            in_specs=in_specs,
            out_specs=(whole(d), summed(d), summed(dv_)),
            out_shape=(jax.ShapeDtypeStruct((b * h, L, d), q.dtype),
                       jax.ShapeDtypeStruct((b * hk, L, d), jnp.float32),
                       jax.ShapeDtypeStruct((b * hk, L, dv_), jnp.float32)),
            scratch_shapes=([pltpu.VMEM((L, d), jnp.float32)] if nk > 1
                            else []),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=('parallel', 'arbitrary', 'arbitrary'),
                vmem_limit_bytes=_bwd_vmem_limit(
                    L, d, bq, bk, q.dtype.itemsize, dv_, has_doc,
                    grouped=True)),
            interpret=interpret,
        )(*args)
        return (dq.reshape(b, h, L, d),
                dk.reshape(b, hk, L, d).astype(k.dtype),
                dv.reshape(b, hk, L, dv_).astype(v.dtype))

    return spmd_kernel(call, dims, [_BHLD] * 3, _ROLES,
                       scope='flash_attention.pallas')(*args)


# ---------------------------------------------------------------------------
# custom-vjp wrapper + public entry
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11))
def _flash(q, k, v, kpad_bias, seed, doc, causal, scale, block_q,
           block_k, dropout_p, interpret):
    o, _ = _flash_forward(q, k, v, kpad_bias, seed, doc, causal, scale,
                          block_q, block_k, dropout_p, interpret)
    return o


def _flash_fwd_rule(q, k, v, kpad_bias, seed, doc, causal, scale,
                    block_q, block_k, dropout_p, interpret):
    o, lse = _flash_forward(q, k, v, kpad_bias, seed, doc, causal,
                            scale, block_q, block_k, dropout_p, interpret)
    return o, (q, k, v, o, lse, kpad_bias, seed, doc)


def _flash_bwd_rule(causal, scale, block_q, block_k, dropout_p, interpret,
                    res, g):
    q, k, v, o, lse, kpad_bias, seed, doc = res
    dq, dk, dv = _flash_backward(q, k, v, o, lse, kpad_bias, seed, doc,
                                 g, causal, scale, block_q, block_k,
                                 dropout_p, interpret)
    return dq, dk, dv, None, None, None


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def row_starts(doc_start, window=None):
    """(B, L) int32: the first key each query of a packed causal row sees.
    Its document's first position, or under an attention `window` the later
    of that and `position - window + 1` (the query's own position counts
    among the `window`). The ONE place a window enters: masks, tile bounds
    and tile counts are all made from what this returns."""
    start = doc_start.astype(jnp.int32)
    if window is None:
        return start
    at = jnp.arange(start.shape[1], dtype=jnp.int32)[None, :]
    return jnp.maximum(start, at - (int(window) - 1))


def doc_tile_bounds(doc_start, block_q, block_k):
    """The kernels' loop bounds on packed rows, from ``doc_start`` (B, L;
    L in whole tiles of both sizes; any per-row first visible key, a
    document's start or ``row_starts``' under a window), int32:

    lo (B, L // block_q): the first K tile any row of Q tile i can see,
        ``min(doc_start[Q tile i]) // block_k`` (never past the tile of the
        diagonal, whatever a caller's ``doc_start`` holds);
    hi (B, L // block_k): one past the last Q tile with a row that can see a
        key of K tile j, i.e. whose earliest ``doc_start`` is at most the
        tile's last column.

    The minimum over a tile's rows keeps them right for any ``doc_start``,
    monotone along the row or not: a tile pair inside the bounds that no row
    sees is masked by the kernels as every pair is."""
    B, L = doc_start.shape
    nq, nk = L // block_q, L // block_k
    earliest = jnp.min(doc_start.astype(jnp.int32).reshape(B, nq, block_q),
                       axis=2)
    q_tiles = jnp.arange(nq, dtype=jnp.int32)
    lo = jnp.minimum(earliest // block_k, q_tiles * block_q // block_k)
    last_col = jnp.arange(nk, dtype=jnp.int32) * block_k + block_k - 1
    seen = earliest[:, None, :] <= last_col[None, :, None]      # (B, nk, nq)
    hi = jnp.max(jnp.where(seen, q_tiles + 1, 0), axis=2)
    return lo, hi


def doc_tile_counts(doc_start, block_q=_BLOCK, block_k=_BLOCK, window=None):
    """-> (swept, causal), float32 scalars: the tile pairs one forward
    call on these rows visits per head with the document bounds (and the
    `window`'s, where one is given), and without them (every tile up to the
    diagonal). Zeros where the rows do not tile (the kernels do not run
    there)."""
    L = doc_start.shape[1]
    if not _tiles(L, L, block_q, block_k):
        return jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)
    bq, bk = min(block_q, L), min(block_k, L)
    if window is not None:
        doc_start = row_starts(doc_start, window)
    lo, _ = doc_tile_bounds(doc_start, bq, bk)
    ends = (jnp.arange(L // bq, dtype=jnp.int32) * bq + bq + bk - 1) // bk
    return (jnp.sum(ends - lo).astype(jnp.float32),
            (jnp.sum(ends) * lo.shape[0]).astype(jnp.float32))


def _tiles(lq, lk, block_q, block_k):
    return (lk == lq and lq % min(block_q, lq) == 0
            and lq % min(block_k, lq) == 0)


def flash_attention_bhld(q, k, v, causal=False, scale=None, kpad_bias=None,
                         dropout_p=0.0, dropout_seed=None, doc_start=None,
                         window=None, block_q=_BLOCK, block_k=_BLOCK,
                         interpret=False):
    """Flash attention on (B, H, L, D) tensors; v's head size may differ
    from q's and k's, and k and v may hold fewer heads than q (grouped-query
    attention: a divisor of q's count; query head h reads K/V head
    h // group, dK and dV come back at the K/V head count; no bias and no
    dropout there).

    kpad_bias: optional (B, Lk) additive key-padding bias (0 = keep, -1e4/-inf
    style = masked). dropout_p: attention-probability dropout rate; when > 0,
    dropout_seed must be an int32 array of shape (1, 1) (the keep-mask is a
    deterministic function of it). doc_start: optional (B, L) int32, with
    causal=True: the first position of each query's document in a packed
    row; a query then sees the keys from there to itself. window: with
    doc_start, the number of keys a query sees at most, itself among them
    (``row_starts``). Takes plain-XLA
    attention off the TPU (unless interpret mode is asked for) or when L
    doesn't tile; either way the ops sit under a ``flash_attention.pallas`` /
    ``flash_attention.xla`` named scope (``_common.took``).
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    L = q.shape[2]
    dropout_p = float(dropout_p)
    if doc_start is not None and not causal:
        raise ValueError("doc_start describes packed causal rows: it needs "
                         "causal=True")
    if window is not None:
        if doc_start is None:
            raise ValueError("window narrows a packed row's doc_start: give "
                             "both (zeros for a row of one document)")
        doc_start = row_starts(doc_start, window)
    if q.shape[1] != k.shape[1]:
        if q.shape[1] % k.shape[1] or k.shape[1] != v.shape[1]:
            raise ValueError("%d query heads do not group over %d / %d K/V "
                             "heads" % (q.shape[1], k.shape[1], v.shape[1]))
        if kpad_bias is not None or dropout_p > 0.0:
            raise ValueError("grouped-query heads take no key-padding bias "
                             "and no dropout")
    if kpad_bias is not None:
        # the forward kernel streams bias columns with an in-kernel dynamic
        # slice of the minor dim, which Mosaic cannot lower for block_k < L;
        # key-padding attention is non-causal and reads every K anyway, so
        # stream the full row (one K tile: the backward writes dQ directly)
        block_k = L
    usable = pallas_runs(interpret) and _tiles(L, k.shape[2], block_q,
                                               block_k)
    if dropout_p > 0.0 and dropout_seed is None:
        raise ValueError("dropout_p > 0 requires dropout_seed")
    if not usable:
        key = None
        if dropout_p > 0.0:
            key = jax.random.PRNGKey(0)
            key = jax.random.fold_in(key, dropout_seed.reshape(())
                                     .astype(jnp.uint32))
        with took('flash_attention', 'xla'):
            return _attn_reference(q, k, v, causal, scale, kpad_bias,
                                   dropout_p, key, doc_start)
    seed = (dropout_seed if dropout_seed is not None
            else jnp.zeros((1, 1), jnp.int32))
    doc = None
    if doc_start is not None:
        doc = (doc_start,) + doc_tile_bounds(doc_start, min(block_q, L),
                                             min(block_k, L))
    with took('flash_attention', 'pallas'):
        return _flash(q, k, v, kpad_bias, seed, doc, causal, scale,
                      block_q, block_k, dropout_p, interpret)


def _is_key_padding(mask_shape, batch, lk):
    """A (B|1, 1, 1, Lk) mask, the shape BERT-style key-padding masks take:
    the one mask the kernels stream (as a (B, Lk) bias)."""
    return (len(mask_shape) == 4 and mask_shape[1] == 1
            and mask_shape[2] == 1 and mask_shape[3] == lk
            and mask_shape[0] in (1, batch))


def _kpad_bias(mask, batch):
    """The (B, Lk) additive bias of a boolean / additive key-padding mask."""
    bias = mask.reshape((mask.shape[0], mask.shape[3]))
    if bias.dtype == jnp.bool_:
        bias = jnp.where(bias, 0.0, -1e9).astype(jnp.float32)
    if bias.shape[0] == 1:
        bias = jnp.broadcast_to(bias, (batch, bias.shape[1]))
    return bias


def attention_blhd(q, k, v, mask=None, causal=False, dropout_p=0.0,
                   dropout_key=None, doc_start=None):
    """softmax(q k^T / sqrt(D) + mask) v on (B, L, H, D) operands, as
    ``nn.functional.scaled_dot_product_attention`` defines it: mask boolean
    (True = keep) or additive, broadcast against (B, H, Lq, Lk);
    dropout_key: a jax key, required when dropout_p > 0. doc_start: (B, L)
    int32, with causal=True and nothing else: packed rows, each query sees
    the keys from its document's first position to itself; the choice of
    path is then ``flash_attention_bhld``'s (the kernels wherever L tiles).

    The flash kernels on the TPU when they can express the call (Lq == Lk
    in whole tiles, no mask or a key-padding one) and the sequence is at
    least ``_MIN_SEQ`` long; otherwise the scores as one XLA expression.
    Either way under ``flash_attention.pallas`` / ``flash_attention.xla``.
    """
    batch, lq, lk = q.shape[0], q.shape[1], k.shape[1]
    if doc_start is not None:
        if mask is not None or dropout_p > 0.0:
            raise ValueError("doc_start takes no mask and no dropout")
        q, k, v = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
        return jnp.swapaxes(flash_attention_bhld(
            q, k, v, causal=causal, doc_start=doc_start), 1, 2)
    if (pallas_runs(False) and lq >= _MIN_SEQ
            and _tiles(lq, lk, _BLOCK, _BLOCK)
            and (mask is None or _is_key_padding(mask.shape, batch, lk))):
        seed = None
        if dropout_p > 0.0:
            seed = jax.random.randint(dropout_key, (1, 1), 0, 2**31 - 1
                                      ).astype(jnp.int32)
        kpad = None if mask is None else _kpad_bias(mask, batch)
        # (B, L, H, D) -> (B, H, L, D)
        q, k, v = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
        out = flash_attention_bhld(q, k, v, causal=causal, kpad_bias=kpad,
                                   dropout_p=dropout_p, dropout_seed=seed)
        return jnp.swapaxes(out, 1, 2)
    with took('flash_attention', 'xla'):
        scale = 1.0 / math.sqrt(q.shape[-1])
        q, k, v = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
        scores = jnp.einsum('bhld,bhmd->bhlm', q, k) * scale
        if mask is not None:
            if mask.dtype == jnp.bool_:
                scores = jnp.where(mask, scores, NEG_INF)
            else:
                scores = scores + mask
        if causal:
            visible = jnp.tril(jnp.ones(scores.shape[-2:], dtype=bool))
            scores = jnp.where(visible, scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        if dropout_p > 0.0:
            keep = keep_mask(dropout_key, 1.0 - dropout_p, probs.shape,
                             _BHLD[:2] + (None, None))
            probs = jnp.where(keep, probs / (1.0 - dropout_p),
                              jnp.zeros_like(probs))
        out = jnp.einsum('bhlm,bhmd->bhld', probs, v)
        return jnp.swapaxes(out, 1, 2)
