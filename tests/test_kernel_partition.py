"""Kernel sites under a partitioned jit (``kernels._common.spmd_kernel``).

The TPU compiler refuses a Pallas kernel in a GSPMD-partitioned program,
so under ``kernel_mesh`` each site becomes a ``shard_map``. Here on the
8-device CPU mesh, in interpret mode: the partitioned result equals the
one-device result, the kernel really runs per shard, and the shard offsets
the dropout tile ids are built from are the global ones. (The TPU
compiler's side of it is held by tests/test_chip_compile.py.)
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.kernels import _common
from paddle_tpu.kernels.flash_attention import flash_attention_bhld
from paddle_tpu.kernels.fused_dropout_norm import fused_dropout_add_layer_norm
from paddle_tpu.kernels.fused_norm import fused_layer_norm, fused_rms_norm


def _mesh(shape, names):
    n = int(np.prod(shape))
    return Mesh(np.asarray(jax.devices()[:n]).reshape(shape), names)


def _ln(x, w, b):
    return fused_layer_norm(x, w, b, interpret=True)


def _rms(x, w, b):
    return fused_rms_norm(x, w, interpret=True)


def _fdln(x, w, b):
    return fused_dropout_add_layer_norm(x, x * 0.5, w, b, interpret=True)


@pytest.mark.parametrize('fn', [_ln, _rms, _fdln],
                         ids=['layer_norm', 'rms_norm', 'dropout_add_norm'])
def test_row_kernels_partition_over_the_batch_axes(fn):
    mesh = _mesh((4, 2), ('data', 'model'))
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 16, 128), jnp.float32)
    w = jnp.linspace(0.5, 1.5, 128)
    b = jnp.linspace(-1.0, 1.0, 128)

    def loss(x, w, b):
        return jnp.sum(fn(x, w, b) ** 2 * jnp.arange(128.0))

    def traced(x, w, b):
        with _common.kernel_mesh(mesh, ('data',), ('model',)):
            return jax.value_and_grad(loss, argnums=(0, 1, 2))(x, w, b)

    rows, rep = NamedSharding(mesh, P('data')), NamedSharding(mesh, P())
    step = jax.jit(traced, in_shardings=(rows, rep, rep))
    assert 'shard_map' in str(step.trace(x, w, b).jaxpr)
    got_v, got_g = step(x, w, b)
    want_v, want_g = jax.value_and_grad(loss, argnums=(0, 1, 2))(x, w, b)
    np.testing.assert_allclose(got_v, want_v, rtol=1e-5)
    for g, wnt in zip(got_g, want_g):
        np.testing.assert_allclose(g, wnt, rtol=1e-4, atol=1e-3)
    assert got_g[0].sharding.spec[0] == 'data'   # nothing was gathered


@pytest.mark.parametrize('causal,kpad,packed', [
    (True, False, False), (False, True, False), (True, False, True)],
    ids=['causal', 'key_padding', 'packed'])
def test_flash_partitions_over_batch_and_heads(causal, kpad, packed):
    mesh = _mesh((2, 2), ('data', 'model'))
    B, H, L, D = 4, 4, 128, 32
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (B, H, L, D),
                                 jnp.float32) for i in range(3))
    bias = start = None
    if kpad:
        bias = jnp.where(jnp.arange(L)[None, :] < jnp.array(
            [[128], [96], [64], [32]]), 0.0, -1e4).astype(jnp.float32)
    if packed:      # every row its own documents: each device's rows take
        at = jnp.arange(L)[None, :]     # their own tile bounds with them
        start = jnp.where(at >= jnp.array([[128], [64], [100], [32]]),
                          jnp.array([[128], [64], [100], [32]]), 0
                          ).astype(jnp.int32)
    block = 32 if packed else 64

    def loss(q, k, v):
        o = flash_attention_bhld(q, k, v, causal=causal, kpad_bias=bias,
                                 doc_start=start, block_q=block,
                                 block_k=block, interpret=True)
        return jnp.sum(o * o)

    def traced(q, k, v):
        with _common.kernel_mesh(mesh, ('data',), ('model',)):
            return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    bh = NamedSharding(mesh, P('data', 'model'))
    step = jax.jit(traced, in_shardings=(bh, bh, bh))
    assert str(step.trace(q, k, v).jaxpr).count('shard_map') >= 2
    got_v, got_g = step(q, k, v)
    want_v, want_g = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got_v, want_v, rtol=1e-4)
    for g, wnt in zip(got_g, want_g):
        np.testing.assert_allclose(g, wnt, rtol=1e-3, atol=1e-4)
    assert tuple(got_g[0].sharding.spec[:2]) == ('data', 'model')


def test_shard_offsets_are_global_and_uneven_parts_stay_whole():
    """``shard[f] = (start, total)``: start is where the device's part
    begins in the whole array (what the dropout tile ids are built from);
    a factor whose parts would not be a multiple of ``granule`` is not
    split."""
    mesh = _mesh((4,), ('data',))

    def impl(x, shard):
        start, total = shard['n']
        return x + start.astype(x.dtype), jnp.full(x.shape[:1], total)

    site = _common.spmd_kernel(impl, [('n', 'd')], [('n', 'd'), ('n',)],
                               {'n': 'batch'}, granule=8)

    def traced(x):
        with _common.kernel_mesh(mesh, ('data',)):
            return site(x)

    x = jnp.zeros((64, 4))
    starts, totals = jax.jit(traced)(x)
    np.testing.assert_array_equal(starts[:, 0], np.repeat([0, 16, 32, 48],
                                                          16))
    np.testing.assert_array_equal(totals, 64)
    # 24 rows over 4 devices = 6 each: not a multiple of 8 -> whole
    starts, _ = jax.jit(traced)(jnp.zeros((24, 4)))
    np.testing.assert_array_equal(starts, 0)
    # outside the scope the site is the plain call
    starts, totals = jax.jit(site)(x)
    np.testing.assert_array_equal(starts, 0)
    np.testing.assert_array_equal(totals, 64)


def test_site_inside_a_manual_shard_map_is_called_as_it_is():
    """Ring attention, the pipeline and sync-BN call kernels from inside
    their own ``shard_map``: the site must not open a second one over the
    same axes."""
    mesh = _mesh((4,), ('data',))
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 128), jnp.float32)
    w = jnp.ones((128,))

    def body(x):
        with _common.kernel_mesh(mesh, ('data',)):
            return fused_layer_norm(x, w, w * 0, interpret=True)

    got = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P('data'),
                                out_specs=P('data'), check_vma=False))(x)
    np.testing.assert_allclose(got, fused_layer_norm(x, w, w * 0,
                                                     interpret=True),
                               rtol=1e-5, atol=1e-5)
