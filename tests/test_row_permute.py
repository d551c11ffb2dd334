"""The routed experts' row moves (`kernels/row_permute.py`): the two Pallas
kernels in interpret mode against the XLA expressions they replace, values
and gradients, alone and through `expert_share`; the counter they bring; and
which form a call takes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.kernels import row_permute as rp
from paddle_tpu.kernels._common import kernel_mesh
from paddle_tpu.nn.functional import moe

T, H, TILE, TILES = 64, 256, 16, 6

# rows held by each tile (its first ones; none behind the first that holds
# none), and the tokens the rows are drawn from
_LAYOUTS = {
    'every tile full': ([16] * 6, range(T)),
    'partly filled last tiles': ([16, 3, 16, 7, 1, 16], range(T)),
    'tiles behind the last that holds rows': ([16, 5, 0, 0, 0, 0], range(T)),
    'one row': ([1, 0, 0, 0, 0, 0], range(T)),
    'no row at all': ([0] * 6, range(T)),
    # 16 tokens fill every tile: each is held six times, 48 tokens never
    'tokens in every tile and tokens in none': ([16] * 6, range(16)),
}


def layout(name, seed=0):
    """tok (R,), held (tiles,): a tile's rows held belong to different
    tokens, as an expert's do; the rows that hold none name token 0."""
    rows, tokens = _LAYOUTS[name]
    rs = np.random.default_rng(seed)
    tok = np.zeros((TILES, TILE), np.int32)
    for i, n in enumerate(rows):
        tok[i, :n] = rs.permutation(np.asarray(tokens))[:n]
    return jnp.asarray(tok.reshape(-1)), jnp.asarray(rows, jnp.int32)


def close(got, want, dtype):
    tol = 1e-5 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)


@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16],
                         ids=['float32', 'bfloat16'])
@pytest.mark.parametrize('name', sorted(_LAYOUTS))
def test_gather_rows_equals_the_xla_gather(name, dtype):
    """Rows and `d x`; the kernel sums `d x` in float32 and rounds once."""
    tok, held = layout(name)
    rs = np.random.default_rng(1)
    x = jnp.asarray(rs.normal(size=(T, H)), dtype)
    cot = jnp.asarray(rs.normal(size=(TILES * TILE, H)), jnp.float32)

    def loss(x, interpret):
        rows = rp.gather_rows(x, tok, held, interpret=interpret)
        assert rows.dtype == x.dtype
        return jnp.sum(rows.astype(jnp.float32) * cot), rows
    (_, rows), dx = jax.value_and_grad(
        lambda x: loss(x, True), has_aux=True)(x)
    (_, want), want_dx = jax.value_and_grad(
        lambda x: loss(x, False), has_aux=True)(x)
    np.testing.assert_array_equal(np.asarray(rows, np.float32),
                                  np.asarray(want, np.float32))
    assert dx.dtype == x.dtype
    close(dx, want_dx, dtype)
    valid = np.asarray(rp.rows_valid(held, TILE))
    assert not np.asarray(rows, np.float32)[~valid].any()


@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16],
                         ids=['float32', 'bfloat16'])
@pytest.mark.parametrize('name', sorted(_LAYOUTS))
def test_combine_rows_equals_the_xla_scatter_add(name, dtype):
    """The sum by token, `d out` and the weights' gradient; the rows that
    hold no token neither add nor take a gradient, whatever lies there."""
    tok, held = layout(name)
    rs = np.random.default_rng(2)
    out = jnp.asarray(rs.normal(size=(TILES * TILE, H)), dtype)
    scale = jnp.asarray(rs.uniform(0.2, 1.0, size=(TILES * TILE,)),
                        jnp.float32)
    cot = jnp.asarray(rs.normal(size=(T, H)), jnp.float32)

    def loss(out, scale, interpret):
        y = rp.combine_rows(out, scale, tok, held, T, interpret=interpret)
        assert y.dtype == jnp.float32
        return jnp.sum(y * cot), y
    (_, y), (dout, dscale) = jax.value_and_grad(
        lambda *a: loss(*a, True), argnums=(0, 1), has_aux=True)(out, scale)
    (_, want), (want_dout, want_dscale) = jax.value_and_grad(
        lambda *a: loss(*a, False), argnums=(0, 1), has_aux=True)(out, scale)
    close(y, want, jnp.float32)
    assert dout.dtype == out.dtype
    close(dout, want_dout, dtype)
    close(dscale, want_dscale, jnp.float32)
    valid = np.asarray(rp.rows_valid(held, TILE))
    assert not np.asarray(dout, np.float32)[~valid].any()
    assert not np.asarray(dscale)[~valid].any()
    never = np.setdiff1d(np.arange(T), np.asarray(tok)[valid])
    assert not np.asarray(y)[never].any()


def _picks(tokens, k, experts, rows):
    """idx (T, k): `rows[e]` tokens pick held expert e (the first rows[e]
    tokens, so a token may pick several), every other pick goes to the
    experts from 8 on, which nobody holds."""
    idx = np.tile(np.arange(8, 8 + k), (tokens, 1))
    for slot, (e, n) in enumerate(sorted(rows.items())):
        idx[:n, slot] = e
    assert idx.max() < experts and all(len(set(r)) == k for r in idx)
    return jnp.asarray(idx, jnp.int32)


# 128 tokens, top 4 of 32, experts (0, 4) held, tiles of 16 rows: buffers of
# 8 and of 16 tiles (128 and 256 rows)
_ROUTINGS = {
    'an empty expert': ({0: 40, 1: 0, 2: 7, 3: 0}, 1),
    'tokens held by three experts and tokens held by none':
        ({0: 30, 1: 30, 3: 30}, 1),
    'a partly filled last tile and tiles that hold no row':
        ({0: 17, 2: 1}, 1),
    'the larger buffer': ({0: 60, 1: 60, 2: 60}, 1),
    'more than one round': ({0: 128, 1: 128, 2: 128}, 2),
    'no row at all': ({}, 1),
}


@pytest.mark.parametrize('dtype', [None, jnp.bfloat16],
                         ids=['float32', 'bfloat16'])
@pytest.mark.parametrize('routing', sorted(_ROUTINGS))
def test_expert_share_through_the_kernels(routing, dtype):
    """The routed sum and the gradients of x, of the routing weights and of
    the three weight stacks with the rows moved by the kernels, against the
    same call with the XLA expressions (the products are the same kernels in
    both); what the kernels moved is what the share holds."""
    rows, rounds = _ROUTINGS[routing]
    tokens, E, k, held = 128, 32, 4, (0, 4)
    width, F = 256, 128
    rs = np.random.default_rng(3)
    x = jnp.asarray(rs.normal(size=(tokens, width)), jnp.float32)
    gate, up = (jnp.asarray(rs.normal(size=(4, width, F)) * width ** -0.5,
                            jnp.float32) for _ in range(2))
    down = jnp.asarray(rs.normal(size=(4, F, width)) * F ** -0.5,
                       jnp.float32)
    idx = _picks(tokens, k, E, rows)
    w = jnp.asarray(rs.uniform(0.2, 1.0, size=(tokens, k)), jnp.float32)
    cot = jnp.asarray(rs.normal(size=(tokens, width)), jnp.float32)
    assert moe.buffer_tiles(tokens, k, 4, E, 16) == (8, 16)

    def program(x, w, gate, up, down):
        y, c = moe.expert_share(x, idx, w, gate, up, down, held, E,
                                tile=16, dtype=dtype, interpret=True)
        return jnp.sum(y * cot), (y, c)

    def run(kernels):
        # (`_round` is a jit of its own, keyed by its arguments and not by
        # what the patch puts in its way)
        with pytest.MonkeyPatch.context() as patch:
            for name in () if kernels else ('gather_rows', 'combine_rows'):
                patch.setattr(moe, name, lambda *a, real=getattr(rp, name),
                              **kw: real(*a, **{**kw, 'interpret': False}))
            moe._round.clear_cache()
            try:
                return jax.value_and_grad(
                    program, argnums=(0, 1, 2, 3, 4), has_aux=True)(
                        x, w, gate, up, down)
            finally:
                moe._round.clear_cache()
    (_, (y, c)), got = run(True)
    (_, (want_y, _)), want = run(False)
    tol = 2e-5 if dtype is None else 5e-2
    np.testing.assert_allclose(y, want_y, atol=tol, rtol=tol)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=tol * 5, rtol=tol)
    if any(rows.values()):
        assert all(float(jnp.max(jnp.abs(g))) > 0 for g in got)
    c = dict(zip(moe.COUNTERS, np.asarray(c).tolist()))
    assert c['rounds'] == rounds and c['dropped'] == 0.0
    assert c['assignments_held'] == sum(rows.values())
    assert c['rows_moved'] == c['assignments_held'] - c['dropped']
    assert c['rows_moved'] <= c['rows_computed']


@pytest.mark.parametrize('case,path', [
    ('on the tpu', 'pallas'), ('in interpret mode', 'pallas'),
    ('off the tpu', 'xla'),
    ('tiles that are no whole sublane tiles', 'xla'),
    ('a width that is no whole lane register', 'xla'),
    ('bfloat16 rows whose words are no whole lane register', 'xla'),
    ('a token side no chunk of which fits', 'xla'),
    ('a step sharded over a mesh', 'xla')])
@pytest.mark.parametrize('kernel', ['gather_rows', 'combine_rows'])
def test_which_form_a_call_takes(kernel, case, path, monkeypatch):
    """The rule lives with the kernels: the backend, the tiling, a chunk of
    the token side that fits VMEM, and whether the trace lies in a sharded
    step; either way under `row_permute.<path>` and counted. bfloat16 rows
    whose pairs fill no whole registers (384: 1.5; a hidden size of 2688:
    10.5) are GATHERED by the kernels all the same, as float32 words (PR
    43); `combine_rows` is never handed such rows by the expert layer."""
    if case.startswith('bfloat16') and kernel == 'gather_rows':
        path = 'pallas'
    if case not in ('off the tpu', 'in interpret mode'):
        monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    tile = 4 if 'sublane' in case else 16
    width = 192 if case.startswith('a width') else \
        384 if case.startswith('bfloat16') else 256
    dtype = jnp.bfloat16 if case.startswith('bfloat16') else jnp.float32
    tokens = (rp._RESIDENT // (128 * 4) + 8) if 'fits' in case else 64
    rows = 4 * tile
    ints = [jax.ShapeDtypeStruct((rows,), jnp.int32),
            jax.ShapeDtypeStruct((4,), jnp.int32)]
    interpret = case == 'in interpret mode'
    if kernel == 'gather_rows':
        floats = [jax.ShapeDtypeStruct((tokens, width), dtype)]

        def call(x, tok, held):
            return rp.gather_rows(x, tok, held, interpret=interpret)
    else:
        floats = [jax.ShapeDtypeStruct((rows, width), dtype),
                  jax.ShapeDtypeStruct((rows,), jnp.float32)]

        def call(out, scale, tok, held):
            return rp.combine_rows(out, scale, tok, held, tokens,
                                   interpret=interpret)

    def site(*a):
        if 'mesh' in case:
            mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ('data',))
            with kernel_mesh(mesh, ('data',)):
                return call(*a)
        return call(*a)
    was = obs.enabled()
    obs.enable()
    try:
        before = obs.counter('kernels.row_permute.%s' % path).value
        text = str(jax.make_jaxpr(site)(*floats, *ints))
        after = obs.counter('kernels.row_permute.%s' % path).value
    finally:
        if not was:
            obs.disable()
    assert after == before + 1
    assert ('pallas_call' in text) == (path == 'pallas')
    assert (' gather[' in text or ' scatter-add[' in text) == (path == 'xla')
