"""Checks of the Pallas kernels that only a chip can make.

Interpret mode stubs the TPU's hardware PRNG to zeros, so the in-kernel
dropout has no CPU test: ``chip_smoke.py`` runs these on the device. Each
raises on failure.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ._common import kernel_mesh
from .delta_rule import delta_rule
from .flash_attention import _attn_reference, flash_attention_bhld
from .fused_dropout_norm import fused_dropout_add_layer_norm
from .short_conv import short_conv


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _qkv_program(key, batch, heads, seq, head_dim, dtype):
    return tuple(jax.random.normal(kk, (batch, heads, seq, head_dim), dtype)
                 for kk in jax.random.split(key, 3))


def make_device_qkv(batch, heads, seq, head_dim, dtype, seed=0):
    """Three [b,h,s,d] standard-normal tensors generated ON DEVICE as one
    jitted program (compiled once per shape signature per process, zero
    host->device transfer)."""
    return _qkv_program(jax.random.PRNGKey(seed), batch, heads, seq,
                        head_dim, jnp.dtype(dtype))


def _padding_bias(b, L, half_of_last=False):
    """(b, L) additive key-padding bias: the last fifth of row 0's keys
    (and, asked for, the last half of the last row's) are padding."""
    keep = np.ones((b, L), bool)
    keep[0, L - L // 5:] = False
    if half_of_last:
        keep[-1, L // 2:] = False
    return jnp.where(jnp.asarray(keep), 0.0, -1e4).astype(jnp.float32)


def _check_hw_dropout(what, fn, x):
    """``fn(x, seed)`` draws its mask from the TPU hardware PRNG: it must be
    deterministic under a fixed seed, seed-sensitive, and differentiable
    with finite gradients. Raises on any failure."""
    f = jax.jit(lambda s: fn(x, s))
    s1 = jnp.array([[1234]], jnp.int32)
    o1, o2, o3 = f(s1), f(s1), f(jnp.array([[77]], jnp.int32))
    if not bool(jnp.array_equal(o1, o2)):
        raise AssertionError('%s dropout: not deterministic under a fixed '
                             'seed' % what)
    if bool(jnp.allclose(o1, o3)):
        raise AssertionError('%s dropout: the seed has no effect' % what)
    g = jax.jit(jax.grad(lambda xx: jnp.sum(fn(xx, s1) ** 2)))(x)
    if not bool(jnp.isfinite(g).all()):
        raise AssertionError('%s dropout: non-finite gradients' % what)


def check_flash_dropout(shape=(1, 4, 512, 64), interpret=False):
    """The in-kernel hardware-PRNG attention dropout."""
    q, k, v = make_device_qkv(*shape, jnp.float32)
    _check_hw_dropout('flash', lambda qq, seed: flash_attention_bhld(
        qq, k, v, causal=True, dropout_p=0.3, dropout_seed=seed,
        block_q=256, block_k=256, interpret=interpret), q)


def check_flash_dropout_backward(shape=(2, 16, 512, 64), dropout_p=0.1,
                                 interpret=False):
    """The dropout-ON backward against finite differences of its own
    forward. Forward and backward each rebuild the keep mask from the
    hardware PRNG; no reference can follow it, and a backward that rebuilt
    another mask than the forward's would still be finite and
    deterministic. Under a fixed seed the loss is a smooth function of q,
    k and v, so for a direction u of each, ``(loss(x + eps u) - loss(x -
    eps u)) / 2 eps`` must equal ``<grad, u>``: fp32 operands and fp32
    matmuls (``highest``), a key-padding bias, the default blocks. Returns
    ``{name: (finite difference, <grad, u>)}``; raises where they differ
    by over 1% (fp32 rounding of the loss reads 1e-4 on the chip)."""
    eps, tol = 1e-2, 1e-2
    b, h, L, d = shape
    q, k, v = make_device_qkv(b, h, L, d, jnp.float32)
    u = make_device_qkv(b, h, L, d, jnp.float32, seed=1)
    weight = make_device_qkv(b, h, L, d, jnp.float32, seed=2)[0]
    bias = _padding_bias(b, L)
    seed = jnp.array([[1234]], jnp.int32)

    def loss(q, k, v):
        o = flash_attention_bhld(q, k, v, kpad_bias=bias,
                                 dropout_p=dropout_p, dropout_seed=seed,
                                 interpret=interpret)
        return jnp.sum(o * weight)

    @jax.jit
    def readings(q, k, v):
        grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        out = []
        for i, (g, ui) in enumerate(zip(grads, u)):
            def at(t):
                x = [q, k, v]
                x[i] = x[i] + t * ui
                return loss(*x)
            out.append(((at(eps) - at(-eps)) / (2 * eps), jnp.sum(g * ui)))
        return out

    with jax.default_matmul_precision('highest'):
        got = {n: (float(fd), float(an))
               for n, (fd, an) in zip('qkv', readings(q, k, v))}
    for n, (fd, an) in got.items():
        if not abs(fd - an) <= tol * max(abs(fd), abs(an)):
            raise AssertionError(
                'flash dropout backward: d%s along a direction reads %g, '
                'finite differences of the forward %g: the backward does '
                'not differentiate the forward it ran with' % (n, an, fd))
    return got


def _directional(loss, x, noise, eps, names):
    """For each operand of ``loss(*x)``, along a direction u of it (noise
    plus the gradient's own direction at the noise's length, clipped to
    [-4, 4]): ``(loss(x + eps u) - loss(x - eps u)) / 2 eps`` and
    ``<grad, u>``, one jitted program. -> ``{name: (the two)}``."""
    @jax.jit
    def readings(*x):
        grads = jax.grad(loss, argnums=tuple(range(len(x))))(*x)
        out = []
        for i, (g, z) in enumerate(zip(grads, noise)):
            u = jnp.clip(z + g * jnp.sqrt(jnp.sum(z * z) / jnp.sum(g * g)),
                         -4.0, 4.0)

            def moved(t):
                return loss(*x[:i], x[i] + t * u, *x[i + 1:])
            out.append(((moved(eps) - moved(-eps)) / (2 * eps),
                        jnp.sum(g * u)))
        return out
    return {n: (float(fd), float(an))
            for n, (fd, an) in zip(names, readings(*x))}


def _hold_directional(what, got, tol):
    for n, (fd, an) in got.items():
        if not abs(fd - an) <= tol * max(abs(fd), abs(an)):
            raise AssertionError(
                '%s backward: d%s along a direction reads %g, finite '
                'differences of the forward %g' % (what, n, an, fd))
    return got


def check_delta_rule_backward(shape=(1, 1024, 4, 128), interpret=False):
    """The delta rule's backward kernel against finite differences of its
    forward kernel: for a direction u of each of q, k, v, g and beta,
    ``(loss(x + eps u) - loss(x - eps u)) / 2 eps`` must equal
    ``<grad, u>``. float32 operands, every product at ``highest``, four
    documents whose boundaries fall inside sub-blocks. A direction is
    noise plus the gradient's own direction at the noise's length, clipped
    to [-4, 4] (alone, noise gives a derivative that is a sum of terms of
    both signs, small beside the rounding of the two losses; g stays below
    zero on both sides). Returns ``{name: (finite difference, <grad, u>)}``;
    raises where they differ by over 1%."""
    eps, tol = 1e-2, 1e-2
    B, T, H, K = shape
    keys = jax.random.split(jax.random.PRNGKey(0), 11)
    wide, narrow = (B, T, H, K), (B, T, H)

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True))

    x = [unit(jax.random.normal(keys[0], wide)),
         unit(jax.random.normal(keys[1], wide)),
         jax.random.normal(keys[2], wide),
         -0.1 - jax.nn.softplus(jax.random.normal(keys[3], wide)),
         jax.nn.sigmoid(jax.random.normal(keys[4], narrow))]
    noise = [jax.random.normal(key, t.shape) for key, t in zip(keys[5:10], x)]
    weight = jax.random.normal(keys[10], wide)
    at = jnp.arange(T, dtype=jnp.int32)
    seg = (at * 3 // T + (at >= T // 2 + 5))[None].repeat(B, 0)

    def loss(*x):
        return jnp.sum(weight * delta_rule(*x, seg, K ** -0.5,
                                           interpret=interpret))

    with jax.default_matmul_precision('highest'):
        got = _directional(loss, x, noise, eps, ('q', 'k', 'v', 'g', 'beta'))
    return _hold_directional('delta rule', got, tol)


def check_short_conv_backward(shape=(1, 1024, 512), head_dim=128,
                              interpret=False):
    """The short convolution's backward kernel against finite differences of
    its forward kernel, as `check_delta_rule_backward` holds the delta
    rule's: for a direction u of the input and of the taps,
    ``(loss(x + eps u) - loss(x - eps u)) / 2 eps`` must equal
    ``<grad, u>``. float32 operands, with the l2norm and without, documents
    that begin on a tile's first row, inside a tile and two rows before a
    tile's end. Returns ``{name: (finite difference, <grad, u>)}``; raises
    where they differ by over 1%."""
    eps, tol = 1e-2, 1e-2
    B, T, W = shape
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    x = [jax.random.normal(keys[0], shape),
         0.5 * jax.random.normal(keys[1], (4, W))]
    noise = [jax.random.normal(key, t.shape) for key, t in zip(keys[2:4], x)]
    weight = jax.random.normal(keys[4], shape)
    at = jnp.arange(T, dtype=jnp.int32)
    seg = ((at >= T // 4).astype(jnp.int32) + (at >= T // 2 + 5)
           + (at >= 3 * T // 4 - 2))[None].repeat(B, 0)

    got = {}
    for tag, norm in (('', head_dim), ('_no_norm', None)):
        def loss(y, w):
            return jnp.sum(weight * short_conv(y, w, seg, norm,
                                               interpret=interpret))
        got.update(_directional(loss, x, noise, eps, ('y' + tag, 'w' + tag)))
    return _hold_directional('short conv', got, tol)


def check_norm_dropout(rows=1024, hidden=1024, interpret=False):
    """The same checks for the fused dropout+add+LayerNorm kernel."""
    kx, kr = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (rows, hidden), jnp.float32)
    res = jax.random.normal(kr, (rows, hidden), jnp.float32)
    w = jnp.ones((hidden,), jnp.float32)
    b = jnp.zeros((hidden,), jnp.float32)
    _check_hw_dropout('norm', lambda xx, seed: fused_dropout_add_layer_norm(
        xx, res, w, b, dropout_p=0.3, dropout_seed=seed,
        interpret=interpret), x)


def check_flash_against_reference(shape, interpret=False):
    """Kernel output vs ``_attn_reference`` (fp32) without dropout: causal,
    and non-causal with a key-padding bias. Returns the max abs errors."""
    b, h, L, d = shape
    q, k, v = make_device_qkv(b, h, L, d, jnp.bfloat16)
    bias = _padding_bias(b, L, half_of_last=True)
    errs = {}
    for name, causal, kpad in (('causal', True, None),
                               ('key_padding', False, bias)):
        got = jax.jit(lambda a, b_, c: flash_attention_bhld(
            a, b_, c, causal=causal, kpad_bias=kpad,
            interpret=interpret))(q, k, v)
        want = _attn_reference(
            *(t.astype(jnp.float32) for t in (q, k, v)), causal,
            d ** -0.5, kpad)
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
        if not err < 3e-2:      # bf16 in/out, fp32 accumulate, |o| <~ 1
            raise AssertionError('flash %s: max abs error %g vs the XLA '
                                 'reference' % (name, err))
        errs[name] = err
    return errs


def packed_doc_starts(rows, seq, seed, median=1024, sigma=1.0, least=64):
    """(rows, seq) int32 ``doc_start`` of rows filled exactly with documents
    whose lengths are log-normal (clipped to [least, seq], the last cut to
    fit): how the benchmark's packed traffic draws them."""
    rs = np.random.default_rng(seed)
    starts = np.zeros((rows, seq), np.int32)
    for row in starts:
        at = 0
        while at < seq:
            n = int(np.clip(np.rint(rs.lognormal(np.log(median), sigma)),
                            least, seq))
            row[at:at + n] = at
            at += n
    return starts


def check_flash_packed(shape=(1, 2, 8192, 192), v_dim=128, seed=0,
                       block=512, interpret=False):
    """The kernels on packed rows, with their loops bounded by the
    documents, at latent attention's head sizes (q and k ``shape[-1]``
    wide, v ``v_dim``): output and the three gradients against
    ``_attn_reference`` in float32, which masks every (query, key) pair of
    the whole row. Returns the largest errors, each over the reference's
    largest entry, and the share of the causal tile pairs the forward
    visits."""
    from .flash_attention import doc_tile_counts
    b, h, L, d = shape
    q, k, v = make_device_qkv(b, h, L, d, jnp.bfloat16, seed)
    v = v[..., :v_dim]
    start = jnp.asarray(packed_doc_starts(b, L, seed))
    cot = jnp.cos(jnp.arange(v_dim, dtype=jnp.float32))

    def flash_loss(q, k, v):
        o = flash_attention_bhld(q, k, v, causal=True, doc_start=start,
                                 block_q=block, block_k=block,
                                 interpret=interpret)
        return jnp.sum(o.astype(jnp.float32) * cot), o

    def ref_loss(q, k, v):
        o = _attn_reference(q, k, v, True, d ** -0.5, doc_start=start)
        return jnp.sum(o * cot), o

    (_, o), grads = jax.jit(jax.value_and_grad(
        flash_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    with jax.default_matmul_precision('float32'):
        (_, o_ref), want = jax.jit(jax.value_and_grad(
            ref_loss, argnums=(0, 1, 2), has_aux=True))(
            *(t.astype(jnp.float32) for t in (q, k, v)))
    errs = {}
    for name, got, ref in zip(('o', 'dq', 'dk', 'dv'), (o,) + grads,
                              (o_ref,) + want):
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - ref))
                    / jnp.max(jnp.abs(ref)))
        if not err < 2e-2:      # bf16 in/out, fp32 accumulate
            raise AssertionError('packed flash: %s is %g of the '
                                 "reference's largest entry off the XLA "
                                 'reference' % (name, err))
        errs[name] = err
    swept, causal = doc_tile_counts(start, block, block)
    errs['tiles_swept_share'] = float(swept / causal)
    return errs


def check_rotary(rows=2, seq=8192, q_heads=36, latent_heads=32,
                 interpret=False):
    """The rotary kernel (`kernels/rotary.py`) against the XLA form it
    replaces, forward and `jax.vjp`, bfloat16 rows packed from documents, at
    the two rotary cells' shapes: `halves` over 36 heads of 128 (Mellum2's 32
    query and 4 K/V heads) and `pairs` over the last 64 lanes of 32 heads of
    128 + 64 (JoyAI's queries). The XLA form here is written out from
    `rotate_halves` / `rotate_pairs` and a `swapaxes`, as the layers had it.
    Both sides turn in float32 and round once, so they may differ by one
    bfloat16 rounding and no more. Returns the largest difference over the
    largest entry, per result; raises past 2^-7."""
    from .rotary import (rope_inv_freq, rotary_halves, rotary_pairs,
                         rotate_halves, rotate_pairs)
    at = jnp.arange(seq, dtype=jnp.int32)[None] \
        - jnp.asarray(packed_doc_starts(rows, seq, 0, median=seq // 8))
    inv_freq = rope_inv_freq(500000.0, 128).astype(np.float32)
    factor, theta = 1.2772588722239782, 32e6

    def halves_xla(x):
        angle = at.astype(jnp.float32)[..., None] * inv_freq
        angle = jnp.concatenate([angle, angle], -1)[:, :, None]
        return rotate_halves(x, factor * jnp.cos(angle),
                             factor * jnp.sin(angle))

    def pairs_xla(x):
        return jnp.concatenate([x[..., :128].astype(jnp.float32),
                                rotate_pairs(x[..., 128:], at, theta)], -1)

    errs = {}
    for name, heads, d, kernel, xla in (
            ('halves', q_heads, 128, lambda x: rotary_halves(
                x, at, inv_freq, factor, interpret=interpret), halves_xla),
            ('pairs', latent_heads, 192, lambda x: rotary_pairs(
                x, at, theta, 64, interpret=interpret), pairs_xla)):
        kx, kc = jax.random.split(jax.random.PRNGKey(len(errs)))
        x = jax.random.normal(kx, (rows, seq, heads, d), jnp.bfloat16)
        cot = jax.random.normal(kc, (rows, heads, seq, d), jnp.bfloat16)

        def both(f):
            y, pull = jax.vjp(f, x)
            return y, pull(cot)[0]
        got = jax.jit(lambda: both(kernel))()
        want = jax.jit(lambda: both(lambda x: jnp.swapaxes(
            xla(x).astype(x.dtype), 1, 2)))()
        for tag, a, b in zip(('', '_dx'), got, want):
            a, b = a.astype(jnp.float32), b.astype(jnp.float32)
            err = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
            if a.shape != b.shape or not err < 2 ** -7:
                raise AssertionError(
                    'rotary %s%s: the kernel is %g of the largest entry off '
                    'the XLA form, more than one bfloat16 rounding'
                    % (name, tag, err))
            errs[name + tag] = err
    return errs


def check_partitioned(mesh, axis, shape=(8, 16, 512, 64), hidden=1024,
                      dropout_p=0.1, interpret=False):
    """Flash attention and fused dropout+add+LayerNorm, forward and
    backward, in a jit whose operands are split over ``mesh``'s ``axis``
    (as the engine's sharded steps trace them, under ``kernel_mesh``)
    against the same call on one device with the same seed. The dropout
    tiles are keyed on their place in the WHOLE array, so the masks — and
    with them outputs and gradients — must agree whatever the partitioning.
    Returns the max abs differences."""
    b, h, L, d = shape
    q, k, v = make_device_qkv(b, h, L, d, jnp.bfloat16)
    bias = _padding_bias(b, L)
    kx, kr = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(kx, (b * L, hidden), jnp.bfloat16)
    res = jax.random.normal(kr, (b * L, hidden), jnp.bfloat16)
    w = jnp.ones((hidden,), jnp.bfloat16)
    seed = jnp.array([[4321]], jnp.int32)

    def flash(q, k, v, bias, seed):
        def loss(q, k, v):
            o = flash_attention_bhld(q, k, v, kpad_bias=bias,
                                     dropout_p=dropout_p, dropout_seed=seed,
                                     interpret=interpret)
            return jnp.sum(o.astype(jnp.float32) ** 2), o
        (_, o), g = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                       has_aux=True)(q, k, v)
        return (o,) + g

    def norm(x, res, w, seed):
        def loss(x, res, w):
            y = fused_dropout_add_layer_norm(
                x, res, w, w * 0, dropout_p=dropout_p, dropout_seed=seed,
                interpret=interpret)
            return jnp.sum(y.astype(jnp.float32) ** 2), y
        (_, y), g = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                       has_aux=True)(x, res, w)
        return (y,) + g

    def split(f):
        def traced(*args):
            with kernel_mesh(mesh, (axis,)):
                return f(*args)
        return traced

    rows, rep = NamedSharding(mesh, P(axis)), NamedSharding(mesh, P())
    diffs = {}
    # (outputs computed row by row, outputs reduced over the split rows)
    for name, f, args, shardings, n_rowwise in (
            ('flash', flash, (q, k, v, bias, seed),
             (rows, rows, rows, rows, rep), 4),
            ('dropout_add_norm', norm, (x, res, w, seed),
             (rows, rows, rep, rep), 3)):
        want = jax.jit(f)(*args)
        got = jax.jit(split(f), in_shardings=shardings)(*args)
        on = len(got[0].sharding.device_set)
        if on != mesh.size:
            raise AssertionError('%s: the partitioned result sits on %d of '
                                 '%d devices' % (name, on, mesh.size))
        rel = [float(jnp.max(jnp.abs(g.astype(jnp.float32)
                                     - w_.astype(jnp.float32)))
                     / (jnp.max(jnp.abs(w_.astype(jnp.float32))) + 1e-6))
               for g, w_ in zip(got, want)]
        # each device runs the same tiles on the same data: row-wise
        # results agree to a bf16 ulp (2^-7) unless a mask differs (other
        # masks change them by tenths of the largest value); the weight
        # gradients are summed across devices in another order (bf16)
        diffs[name] = {'rowwise': max(rel[:n_rowwise]),
                       'reduced': max(rel[n_rowwise:], default=0.0)}
        if not (diffs[name]['rowwise'] < 1e-2
                and diffs[name]['reduced'] < 2e-2):
            raise AssertionError(
                '%s: partitioned over %d devices differs from one device '
                'with the same seed by %s (relative): the dropout masks '
                'depend on the partitioning' % (name, mesh.size,
                                                diffs[name]))
    return diffs
