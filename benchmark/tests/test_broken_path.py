"""A whole run with the timed path broken underneath has to come out as not
correct. The run skips the harness's look for a chip and drives the rest:
set-up, the checked steps, the window, the reference, the comparison."""
import pytest

import _tiny
from test_reference import LIMITS, compare_rows


class Wrapped:
    """A built step with its call replaced; everything else is the step's."""

    def __init__(self, step):
        self._step = step

    def __getattr__(self, name):
        return getattr(self._step, name)


class Unchanged(Wrapped):
    """A step that returns its state as it got it (and a loss all the same)."""

    def __call__(self, state, batch, key=None):
        import jax
        kept = jax.tree_util.tree_map(lambda v: v + 0, state)
        _, result = self._step(state, batch, key)
        return kept, result


class HalfBatch(Wrapped):
    """A step that trains on the first half of its rows, twice over."""

    def __call__(self, state, batch, key=None):
        import jax
        import jax.numpy as jnp

        def half(v):
            n = v.shape[0] // 2
            return jnp.concatenate([v[:n], v[:n]])
        return self._step(state, jax.tree_util.tree_map(half, batch), key)


@pytest.mark.parametrize('family_name,broken,numbers', [
    ('bert', Unchanged, {'change_gap', 'first_gradient_gap'}),
    ('resnet', Unchanged, {'change_gap', 'first_gradient_gap'}),
    ('bert', HalfBatch, {'first_gradient_gap', 'first_gradient_difference'}),
    ('resnet', HalfBatch, {'first_gradient_gap', 'loss_gap'}),
])
def test_broken_step_is_not_correct(family_name, broken, numbers,
                                    monkeypatch, capsys):
    """LIMITS carries the names the cells' limits files use
    (test_reference.py holds them to that)."""
    result = _tiny.drive(family_name, limits=LIMITS[family_name],
                         wrap_step=broken, monkeypatch=monkeypatch)
    failed = {r['number'] for r in compare_rows(capsys.readouterr().out)
              if not r['ok']}
    assert not result['correct']
    assert numbers <= failed, failed
