"""The rotary kernel (`kernels/rotary.py`) in interpret mode against the XLA
forms it replaces, `rotate_halves` and `rotate_pairs` with the `swapaxes`
behind them: values and `jax.vjp`, both partner lanes, bfloat16 and float32,
a YaRN table, positions that restart inside a packed row, a 128 + 64 head of
which only the last 64 lanes turn, a head of 128 of which only the FIRST 64
turn in the half-split form inside them, the `(B, H, T, d)` layout, and the
property that makes the backward the same kernel: turning by the negated
sine undoes the turn."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import rotary
from paddle_tpu.nn.layer import linear_attention as la

BF16, F32 = jnp.bfloat16, jnp.float32
B, T = 2, 64


def packed_positions(seed=0):
    """(B, T) int32: positions that restart at each document of a row."""
    rs = np.random.default_rng(seed)
    rows = []
    for _ in range(B):
        cuts = np.sort(rs.choice(np.arange(1, T), size=3, replace=False))
        starts = np.concatenate([[0], cuts])
        lengths = np.diff(np.concatenate([starts, [T]]))
        rows.append(np.arange(T) - np.repeat(starts, lengths))
    return jnp.asarray(np.stack(rows), jnp.int32)


def yarn_table(d):
    """A YaRN table whose ramp lies inside the head, and its factor."""
    inv_freq, low, high = la.yarn_inv_freq(500000.0, d, 16.0, 8192)
    assert 0 < low < high < d // 2 - 1
    return inv_freq.astype(np.float32), 0.1 * np.log(16.0) + 1.0


# id -> (the site on x (B, T, H, d), heads, head size)
def halves(inv_freq, factor=1.0):
    def site(x, positions, interpret):
        return rotary.rotary_halves(x, positions, inv_freq, factor,
                                    interpret=interpret)
    return site


def pairs(theta, turned):
    def site(x, positions, interpret):
        return rotary.rotary_pairs(x, positions, theta, turned,
                                   interpret=interpret)
    return site


def yarn_half_table():
    """The YaRN table of 64 of a head's 128 channels (dimension 64, factor
    64 over 4096, beta 64 / 1), and its factor."""
    inv_freq, low, high = la.yarn_inv_freq(500000.0, 64, 64.0, 4096, 64, 1)
    assert (low, high) == (5, 16) and inv_freq.shape == (32,)
    return inv_freq.astype(np.float32), 0.1 * np.log(64.0) + 1.0


_SITES = {
    'halves-plain-table': (halves(la.rope_inv_freq(
        500000.0, 128).astype(np.float32)), 4, 128),
    'halves-first-64-of-128-48-query-heads': (halves(*yarn_half_table()),
                                               48, 128),
    'halves-first-64-of-128-8-kv-heads': (halves(*yarn_half_table()), 8, 128),
    'halves-whole-head-64-query-heads': (halves(la.rope_inv_freq(
        10000.0, 128).astype(np.float32)), 64, 128),
    'halves-first-128-of-256': (halves(la.rope_inv_freq(
        10000.0, 128).astype(np.float32), 0.5), 2, 256),
    'halves-yarn-table-and-factor': (halves(*yarn_table(128)), 4, 128),
    'halves-one-kv-head': (halves(la.rope_inv_freq(
        10000.0, 128).astype(np.float32)), 1, 128),
    'halves-heads-of-two-registers': (halves(la.rope_inv_freq(
        10000.0, 256).astype(np.float32), 0.5), 2, 256),
    'halves-more-heads-than-a-block': (halves(la.rope_inv_freq(
        10000.0, 128).astype(np.float32)), 12, 128),
    'pairs-last-64-of-a-128+64-head': (pairs(32e6, 64), 4, 192),
    'pairs-more-heads-than-a-block': (pairs(1e4, 64), 12, 192),
    'pairs-the-whole-head': (pairs(1e4, 128), 2, 128),
    'pairs-heads-of-half-a-register': (pairs(1e4, 32), 4, 64),
}


def _both(name, dtype, seed=1):
    site, heads, d = _SITES[name]
    rs = np.random.default_rng(seed)
    x = jnp.asarray(rs.normal(size=(B, T, heads, d)), dtype)
    cot = jnp.asarray(rs.normal(size=(B, heads, T, d)), dtype)
    at = packed_positions()
    assert int(at.max()) > 8 and int((at == 0).sum()) == 4 * B
    got, pull = jax.vjp(lambda x: site(x, at, True), x)
    want, pull_xla = jax.vjp(lambda x: site(x, at, False), x)
    return x, (got, pull(cot)[0]), (want, pull_xla(cot)[0])


@pytest.mark.parametrize('dtype', [BF16, F32], ids=['bfloat16', 'float32'])
@pytest.mark.parametrize('name', sorted(_SITES))
def test_the_kernel_equals_the_xla_form(name, dtype):
    """Forward and `jax.vjp`, in the flash kernels' layout and the
    operand's dtype: the turn is float32 on both sides and rounded once, so
    bfloat16 agrees to one rounding and float32 to a few ulps of the
    products."""
    x, (got, dx), (want, want_dx) = _both(name, dtype)
    _, heads, d = _SITES[name]
    assert got.shape == want.shape == (B, heads, T, d)
    assert got.dtype == want.dtype == dx.dtype == want_dx.dtype == dtype
    assert dx.shape == x.shape
    tol = 2e-6 if dtype == F32 else 2 ** -7
    for a, b in ((got, want), (dx, want_dx)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, atol=tol * np.abs(b).max(), rtol=0)


@pytest.mark.parametrize('name', ['halves-yarn-table-and-factor',
                                  'halves-first-64-of-128-8-kv-heads',
                                  'pairs-last-64-of-a-128+64-head'])
def test_the_kernel_took_the_kernel_and_the_xla_form_did_not(name):
    site, heads, d = _SITES[name]
    x = jnp.zeros((B, T, heads, d), BF16)
    at = packed_positions()
    for interpret, path in ((True, 'pallas'), (False, 'xla')):
        text = jax.make_jaxpr(lambda x: site(x, at, interpret))(x) \
            .pretty_print(name_stack=True)
        assert 'rotary.%s' % path in text
        assert ('pallas_call' in text) == (path == 'pallas')


def test_lanes_that_are_not_turned_are_carried_as_they_are():
    """The 128 lanes in front of a 128 + 64 head leave the kernel bit for
    bit, and a position 0 (a document's first token) leaves the whole head
    so."""
    site, heads, d = _SITES['pairs-last-64-of-a-128+64-head']
    rs = np.random.default_rng(3)
    x = jnp.asarray(rs.normal(size=(B, T, heads, d)), BF16)
    at = packed_positions()
    got = np.asarray(site(x, at, True), np.float32)
    want = np.asarray(jnp.swapaxes(x, 1, 2), np.float32)
    np.testing.assert_array_equal(got[..., :128], want[..., :128])
    first = np.asarray(at) == 0
    np.testing.assert_array_equal(got.transpose(0, 2, 1, 3)[first],
                                  want.transpose(0, 2, 1, 3)[first])
    assert np.abs(got[..., 128:] - want[..., 128:]).max() > 0.1


def test_the_first_lanes_turn_and_the_lanes_behind_are_carried():
    """64 of 128: lanes 64-127 leave the kernel bit for bit (no factor on
    them either), lane j < 32 is turned with lane j + 32 and NOT with lane
    j + 64, and a position 0 scales the turned lanes by the factor alone."""
    site, heads, d = _SITES['halves-first-64-of-128-8-kv-heads']
    inv_freq, factor = yarn_half_table()
    rs = np.random.default_rng(3)
    x = jnp.asarray(rs.normal(size=(B, T, heads, d)), F32)
    at = packed_positions()
    got = np.asarray(site(x, at, True)).transpose(0, 2, 1, 3)
    xs = np.asarray(x)
    np.testing.assert_array_equal(got[..., 64:], xs[..., 64:])
    angle = np.asarray(at)[..., None] * inv_freq                 # (B, T, 32)
    z = factor * (xs[..., :32] + 1j * xs[..., 32:64]) \
        * np.exp(1j * angle)[:, :, None, :]
    np.testing.assert_allclose(got[..., :32], z.real, atol=1e-5)
    np.testing.assert_allclose(got[..., 32:64], z.imag, atol=1e-5)
    first = np.asarray(at) == 0
    np.testing.assert_allclose(got[first][..., :64],
                               factor * xs[first][..., :64], rtol=1e-6)


@pytest.mark.parametrize('form,heads,d,turned', [
    ('halves', 4, 128, None), ('halves', 4, 128, 64), ('pairs', 4, 192, None)])
@pytest.mark.parametrize('dtype', [BF16, F32], ids=['bfloat16', 'float32'])
def test_turning_back_by_the_negated_sine_gives_x(form, heads, d, turned,
                                                  dtype):
    """`rotary(rotary(x, sin), -sin) == x` to rounding: the second call is
    the backward's (the same kernel, the BlockSpecs exchanged), fed the
    first one's result."""
    rs = np.random.default_rng(4)
    x = jnp.asarray(rs.normal(size=(B, T, heads * d)), dtype)
    angle = jnp.asarray(rs.uniform(0, 6.28, size=(B, T, d // 2)), F32)
    if form == 'halves' and turned:
        angle = angle[..., :turned // 2]
        angle = jnp.pad(jnp.concatenate([angle, angle], -1),
                        [(0, 0), (0, 0), (0, d - turned)])
        sign = jnp.where(jnp.arange(d) < turned // 2, -1.0, 1.0)
    elif form == 'halves':
        angle = jnp.concatenate([angle, angle], -1)
        sign = jnp.where(jnp.arange(d) < d // 2, -1.0, 1.0)
    else:
        angle = jnp.repeat(angle, 2, -1)
        sign = jnp.where(jnp.arange(d) % 2 == 0, -1.0, 1.0)
    reps = (1, 1, rotary._period(d) // d)
    cos = jnp.tile(jnp.cos(angle), reps)
    sin = jnp.tile(jnp.sin(angle) * sign, reps)
    static = dict(form=form, heads=heads, interpret=True, turned=turned)
    there = rotary._call(x, cos, sin, **static)
    assert there.shape == (B, heads, T, d)
    back = rotary._call(there, cos, -sin, **static)
    assert back.shape == x.shape and back.dtype == x.dtype
    tol = 1e-5 if dtype == F32 else 2 ** -6
    np.testing.assert_allclose(np.asarray(back, np.float32),
                               np.asarray(x, np.float32), atol=tol, rtol=tol)
    assert np.abs(np.asarray(there, np.float32).transpose(0, 2, 1, 3)
                  .reshape(x.shape) - np.asarray(x, np.float32)).max() > 0.1


def test_the_backward_keeps_the_tables_alone():
    """Nothing of x is saved for the backward pass: the residuals of the
    `custom_vjp` are two float32 tables of one period."""
    site, heads, d = _SITES['halves-plain-table']
    x = jnp.zeros((B, T, heads, d), BF16)
    at = packed_positions()
    _, pull = jax.vjp(lambda x: site(x, at, True), x)
    kept = [v for v in jax.tree_util.tree_leaves(pull)
            if hasattr(v, 'shape') and v.size > 1]
    assert sorted((v.shape, v.dtype) for v in kept) == \
        [((B, T, d), F32)] * 2


def test_the_chips_check_holds_in_interpret_mode():
    """`checks.check_rotary` (`chip_smoke.py` runs it at the two cells'
    shapes) at a small size: both forms agree with the XLA form written out
    there, and a kernel that turned the other way would not."""
    from paddle_tpu.kernels import checks
    errs = checks.check_rotary(rows=1, seq=256, q_heads=3, latent_heads=2,
                               interpret=True)
    assert sorted(errs) == ['halves', 'halves_dx', 'pairs', 'pairs_dx']
    assert all(e < 2 ** -7 for e in errs.values())
    with pytest.MonkeyPatch.context() as patch:
        real = rotary._call
        patch.setattr(rotary, '_call', lambda x, cos, sin, **static: real(
            x, cos, -sin, **static))
        with pytest.raises(AssertionError, match='one bfloat16 rounding'):
            checks.check_rotary(rows=1, seq=256, q_heads=3, latent_heads=2,
                                interpret=True)
