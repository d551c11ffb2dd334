"""Shared Pallas kernel utilities (single source for PRNG masks + tiling)."""
import contextlib
import functools
import math
import threading

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P


def took(kernel, path):
    """Say which implementation a kernel entry point took — ``'pallas'``
    or ``'xla'`` (the plain-XLA reference: off-TPU, or a shape that does
    not tile); for ``keep_mask``, ``'shard'`` or ``'whole'``. Bumps
    ``kernels.<kernel>.<path>`` (once per trace) and
    returns the ``jax.named_scope`` that marks the ops in the HLO, so a
    run on the chip can prove which one its step contains."""
    from .. import observability as _obs
    if _obs.enabled():
        _obs.counter('kernels.%s.%s' % (kernel, path)).inc()
    return jax.named_scope('%s.%s' % (kernel, path))


def pallas_runs(interpret):
    """Pallas kernels run on the TPU backend, or anywhere when a test asks
    for interpret mode; every other backend takes the XLA reference."""
    return interpret is not False or jax.default_backend() == 'tpu'


_tls = threading.local()


@contextlib.contextmanager
def kernel_mesh(mesh, batch_axes, head_axes=()):
    """Trace-time scope of a jit whose operands are sharded over ``mesh``
    (the engine's ``sharding=`` / ``in_shardings=`` steps, the Executor's
    data-parallel programs): it tells the kernel sites which mesh axes
    the batch (rows) and the attention heads are split over.

    The TPU compiler refuses a Pallas kernel in such a program ("Mosaic
    kernels cannot be automatically partitioned"), so inside this scope
    every kernel site partitions itself (``spmd_kernel``). Enter it in the
    function being traced. A sharded jit that does not is refused by the
    compiler with that message — nothing silently takes another path.
    """
    prev = getattr(_tls, 'mesh', None)
    _tls.mesh = (mesh, tuple(batch_axes), tuple(head_axes))
    try:
        yield
    finally:
        _tls.mesh = prev


def sharded_step():
    """Whether this trace lies inside `kernel_mesh`: a kernel site whose
    rows belong to no one device (it has nothing `spmd_kernel` could split)
    takes its XLA form there."""
    return getattr(_tls, 'mesh', None) is not None


def _split(roles, total, granule=1):
    """What the entered `kernel_mesh` splits of ``roles``' factors, whose
    whole extents are ``total[f]``: ``(mesh, manual, axes, local)`` with
    ``axes[f]`` the mesh axes over factor ``f`` and ``local[f]`` each
    device's part of it. ``axes`` is empty outside the scope, where no axis
    has more than one device, inside a ``shard_map`` already manual over
    them, and where the parts would not be whole multiples of ``granule``:
    nothing is split then."""
    axes, local = {}, {}
    entered = getattr(_tls, 'mesh', None)
    if entered is None:
        return None, frozenset(), axes, local
    mesh = entered[0]
    by_role = {'batch': entered[1], 'heads': entered[2]}
    manual = frozenset(jax.sharding.get_abstract_mesh().manual_axes)
    for f, role in roles.items():
        names = tuple(a for a in by_role[role]
                      if a not in manual and mesh.shape[a] > 1)
        k = math.prod(mesh.shape[a] for a in names)
        if k > 1 and total[f] % k == 0 and (total[f] // k) % granule == 0:
            axes[f], local[f] = names, total[f] // k
    return mesh, manual, axes, local


def spmd_kernel(impl, in_dims, out_dims, roles, granule=1, scope=None,
                extents=None):
    """Make one ``pallas_call`` site partitionable: under ``kernel_mesh``
    the site becomes a ``shard_map`` in which each device runs the kernel
    on its own rows / (batch, heads) block — no operand is gathered.
    Outside the scope (one device), and inside a ``shard_map`` that is
    already manual over those axes, ``impl`` is called as it is.

    impl(*arrays, shard): the kernel site; ``shard[f]`` is ``(start,
        total)``: where this device's part of factor ``f`` begins (int32
        scalar, 0 when unsharded) and the factor's whole extent (int), so
        tile-keyed dropout masks do not depend on the partitioning.
    in_dims / out_dims: per operand / result, a tuple naming each dim's
        factor (``None``: a dim no device splits).
    roles: ``{factor: 'batch' | 'heads'}`` — the factors the kernel is
        independent along, and which of the scope's axes split each.
    granule: a factor is split only when each device's part is a multiple
        of this (the row kernels need 8 sublanes); otherwise every device
        computes the whole of it.
    scope: the ``took`` scope of the site (``flash_attention.pallas``),
        entered again inside the ``shard_map``: the compiler names a
        custom call after its innermost scope, which is ``shard_map`` there
        otherwise, and a device trace is read by that name.
    extents: ``{factor: whole extent}`` of the factors no operand carries
        (``keep_mask``: its one operand is the key).
    """
    def run(*arrays):
        total = dict(extents or {})
        for dims, a in zip(in_dims, arrays):
            for f, n in zip(dims, a.shape):
                if f in roles:
                    total.setdefault(f, n)
        mesh, manual, axes, local = _split(roles, total, granule)
        if not axes:
            return impl(*arrays,
                        shard={f: (jnp.int32(0), total[f]) for f in roles})

        def on_shard(*arrays):
            with jax.named_scope(scope) if scope else \
                    contextlib.nullcontext():
                return impl(*arrays, shard={
                    f: (jax.lax.axis_index(axes[f]) * local[f] if f in axes
                        else jnp.int32(0), total[f]) for f in roles})

        def spec(dims):
            return P(*(axes.get(f) for f in dims))

        outs = tuple(spec(d) for d in out_dims)
        return jax.shard_map(
            on_shard, mesh=mesh, in_specs=tuple(spec(d) for d in in_dims),
            out_specs=outs if len(outs) > 1 else outs[0],
            axis_names=frozenset(mesh.axis_names) - manual,
            check_vma=False)(*arrays)

    return run


_MASK_ROLES = {'b': 'batch', 'n': 'batch', 'h': 'heads'}


def rows_first(ndim):
    """``keep_mask``'s ``dims`` of an array whose leading dim is the batch
    (rows) and whose other dims no device splits."""
    return (('n',) + (None,) * ndim)[:ndim]


def keep_mask(key, keep_prob, shape, dims):
    """The boolean keep mask of a dropout that XLA ops apply: ``shape``
    independent draws of Bernoulli(``keep_prob``) from ``key``. ``dims``
    names each dim's factor as ``spmd_kernel``'s ``in_dims`` do: ``'b'`` or
    ``'n'`` the batch (rows), ``'h'`` the attention heads, ``None`` a dim no
    device splits (``('b', 'h', None, None)`` for attention's scores).

    Outside ``kernel_mesh``, and wherever ``spmd_kernel`` would split
    nothing, it is ``jax.random.bernoulli(key, keep_prob, shape)``. Inside,
    each device draws its own part, at its part's shape, from
    ``fold_in(key, first)`` with ``first`` the global index where its part
    of each split factor begins: XLA's partitioner cannot split an
    ``rng-bit-generator``, so a draw at the global shape is made WHOLE on
    every device and sliced (on a mesh of k, k times the bits and a copy).

    The mask of a sharded step therefore depends on how the batch was
    split, as jax documents for ``rbg`` keys under any partitioning. The
    kernels' tile-keyed masks (``tile_keep_scale``) stay the same under
    every partition; these are independent draws of the same Bernoulli per
    element under any. The distribution, the ``1 / (1 - p)`` scaling at the
    call site and the precision are as on one device.
    """
    shape, dims = tuple(shape), tuple(dims)
    _, _, axes, _ = _split(*_mask_factors(shape, dims))
    if not axes:
        took('dropout_mask', 'whole')   # counted; the ops stay as they were
        return jax.random.bernoulli(key, keep_prob, shape)
    with took('dropout_mask', 'shard'):
        return _shard_keep_mask(key, keep_prob=keep_prob, shape=shape,
                                dims=dims, entered=_tls.mesh)


def _mask_factors(shape, dims):
    """``keep_mask``'s ``(roles, total)`` as ``spmd_kernel`` names them."""
    total = {f: n for f, n in zip(dims, shape) if f in _MASK_ROLES}
    return {f: _MASK_ROLES[f] for f in total}, total


# A jit of its own: a step's layers draw the same mask shape once each (24
# times in a BERT-large step), and a jit inside the step's trace is traced
# and lowered once for all of them. `entered` is the `kernel_mesh` scope of
# the caller: part of this jit's cache key, and entered again inside.
@functools.partial(jax.jit, static_argnames=('keep_prob', 'shape', 'dims',
                                             'entered'))
def _shard_keep_mask(key, *, keep_prob, shape, dims, entered):
    roles, total = _mask_factors(shape, dims)
    with kernel_mesh(*entered):
        _, _, axes, local = _split(roles, total)
        part = tuple(local.get(f, n) for f, n in zip(dims, shape))

        def draw(key, shard):
            for f in axes:          # no two parts share a stream
                key = jax.random.fold_in(key, shard[f][0])
            return jax.random.bernoulli(key, keep_prob, part)

        return spmd_kernel(draw, [()], [dims], roles, extents=total,
                           scope='dropout_mask.shard')(key)


def head_lanes(size):
    """The lanes a head of `size` channels is laid on: `size` where it is
    whole 128-lane registers; else the next multiple of 128 where the zero
    channels that fill it cost at most a third more work (96 -> 128,
    192 -> 256); None past that (64: the XLA form is cheaper than a kernel
    that does twice the work)."""
    lanes = -(-size // 128) * 128
    return lanes if 3 * lanes <= 4 * size else None


def on_lanes(x, lanes):
    """Zero channels behind the last axis of x up to `lanes`."""
    extra = lanes - x.shape[-1]
    if not extra:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, extra)])


def tile_keep_scale(seed_ref, tile_id, shape, dropout_p):
    """Regenerate a dropout keep/(1-p) mask for one tile from the TPU
    hardware PRNG. Deterministic in (seed, tile_id), so forward and backward
    kernels rebuild the identical mask without ever storing it. Mosaic caps
    prng_seed at 2 values, so callers pre-fold coordinates into tile_id."""
    pltpu.prng_seed(seed_ref[0, 0], tile_id)
    bits = pltpu.prng_random_bits(shape)
    u = jax.lax.bitcast_convert_type(bits, jnp.uint32)
    thresh = jnp.uint32(min(int(dropout_p * 4294967296.0), 4294967295))
    keep = u >= thresh
    return keep.astype(jnp.float32) / (1.0 - dropout_p)


def row_block(n):
    """Largest row-tile size dividing n. Returns None when n has no multiple-
    of-8 tiling (Mosaic requires the sublane dim divisible by 8) — callers
    take the XLA path and say so (``took``)."""
    for bn in (256, 128, 64, 32, 16, 8):
        if n % bn == 0:
            return bn
    return None
