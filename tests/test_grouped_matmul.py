"""The grouped matrix product of the routed experts (`kernels/
grouped_matmul.py`): the Pallas kernels in interpret mode and the XLA form
against a loop over the tiles, forward and backward, with tiles that hold no
rows, groups no tile belongs to, and both operand types."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.kernels import grouped_matmul as gm
from paddle_tpu.kernels._common import kernel_mesh

TM, K, N, G = 16, 128, 256, 4


def tile_loop(lhs, rhs, tile_group, active):
    """Tile i's rows times its group's matrix; zeros from tile `active`."""
    out = []
    for i, g in enumerate(np.asarray(tile_group)):
        rows = lhs[i * TM:(i + 1) * TM].astype(jnp.float32)
        out.append(rows @ rhs[g] if i < active
                   else jnp.zeros((TM, rhs.shape[2]), jnp.float32))
    return jnp.concatenate(out)


def operands(dtype, seed=0):
    rs = np.random.default_rng(seed)
    tiles = 6
    lhs = jnp.asarray(rs.normal(size=(tiles * TM, K)), dtype)
    rhs = jnp.asarray(rs.normal(size=(G, K, N)) * K ** -0.5, jnp.float32)
    cot = jnp.asarray(rs.normal(size=(tiles * TM, N)), jnp.float32)
    return lhs, rhs, cot


# tile -> group (sorted), and how many tiles hold rows
_WALKS = {
    'every tile holds rows': ([0, 0, 1, 2, 3, 3], 6),
    'the last two tiles hold none': ([0, 1, 1, 3, 3, 3], 4),
    'groups 1 and 2 own no tile': ([0, 0, 0, 3, 3, 3], 6),
    'only the first tile holds rows': ([2, 3, 3, 3, 3, 3], 1),
    'no tile holds rows': ([0, 0, 0, 0, 0, 0], 0),
}


@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16],
                         ids=['float32', 'bfloat16'])
@pytest.mark.parametrize('interpret', [True, False],
                         ids=['pallas-interpret', 'xla'])
@pytest.mark.parametrize('walk', sorted(_WALKS))
def test_grouped_matmul_equals_the_loop_over_its_tiles(walk, interpret,
                                                       dtype):
    tile_group, active = _WALKS[walk]
    tile_group = jnp.asarray(tile_group, jnp.int32)
    lhs, rhs, cot = operands(dtype)
    count = jnp.asarray([active], jnp.int32)

    def program(lhs, rhs):
        return jnp.sum(gm.grouped_matmul(
            lhs, rhs, tile_group, count, jnp.float32,
            interpret=interpret) * cot)

    def plain(lhs, rhs):
        cast = rhs.astype(lhs.dtype).astype(jnp.float32)
        return jnp.sum(tile_loop(lhs, cast, tile_group, active) * cot)
    got = jax.value_and_grad(program, argnums=(0, 1))(lhs, rhs)
    want = jax.value_and_grad(plain, argnums=(0, 1))(lhs, rhs)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    scale = 1 + abs(float(want[0]))
    assert abs(float(got[0]) - float(want[0])) < tol * scale
    dlhs, drhs = got[1]
    assert dlhs.dtype == lhs.dtype and drhs.dtype == jnp.float32
    np.testing.assert_allclose(dlhs.astype(jnp.float32),
                               want[1][0].astype(jnp.float32),
                               atol=tol * (2 if dtype == jnp.float32 else 8))
    np.testing.assert_allclose(drhs, want[1][1], atol=tol * 8)
    # a group whose tiles hold no rows takes no gradient, exactly
    reached = set(np.asarray(tile_group)[:active].tolist())
    for g in range(G):
        if g not in reached:
            assert float(jnp.max(jnp.abs(drhs[g]))) == 0.0


def test_rows_behind_the_last_active_tile_are_zeros():
    lhs, rhs, _ = operands(jnp.bfloat16, seed=1)
    out = gm.grouped_matmul(lhs, rhs, jnp.asarray([0, 1, 2, 3, 3, 3],
                                                  jnp.int32),
                            jnp.asarray([3], jnp.int32), interpret=True)
    assert out.dtype == jnp.bfloat16
    assert float(jnp.max(jnp.abs(out[3 * TM:].astype(jnp.float32)))) == 0.0
    assert float(jnp.min(jnp.max(jnp.abs(out[:3 * TM].astype(jnp.float32)),
                                 axis=1))) > 0.0


@pytest.mark.parametrize('case,path', [
    ('on the tpu', 'pallas'), ('off the tpu', 'xla'),
    ('rows that are no whole sublane tiles', 'xla'),
    ('a width that is no whole lane register', 'xla'),
    ('a step sharded over a mesh', 'xla')])
def test_which_form_a_call_takes(case, path, monkeypatch):
    """The rule lives with the kernel: the backend, the tiling, and whether
    the trace lies in a sharded step; either way under
    `grouped_matmul.<path>` and counted."""
    if case != 'off the tpu':
        monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    tm = 8 if 'sublane' in case else TM
    n = 192 if 'lane register' in case else N
    lhs = jax.ShapeDtypeStruct((4 * tm, K), jnp.bfloat16)
    rhs = jax.ShapeDtypeStruct((G, K, n), jnp.float32)
    ints = [jax.ShapeDtypeStruct((4,), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32)]

    def site(lhs, rhs, tile_group, active):
        if 'mesh' in case:
            mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ('data',))
            with kernel_mesh(mesh, ('data',)):
                return gm.grouped_matmul(lhs, rhs, tile_group, active)
        return gm.grouped_matmul(lhs, rhs, tile_group, active)
    was = obs.enabled()
    obs.enable()
    try:
        before = obs.counter('kernels.grouped_matmul.%s' % path).value
        text = str(jax.make_jaxpr(site)(lhs, rhs, *ints))
        after = obs.counter('kernels.grouped_matmul.%s' % path).value
    finally:
        if not was:
            obs.disable()
    assert after == before + 1
    assert ('pallas_call' in text) == (path == 'pallas')
    assert ('ragged_dot' in text) == (path == 'xla')
