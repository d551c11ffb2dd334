"""The kernels layer imports nothing above it.

`paddle_tpu/kernels/` is the bottom of the tree: the layers, the decoders,
the engine, the serving package and `hapi` call into it, never the other way.
A kernel keeps its XLA form beside itself or takes it from `nn.functional`
(pure functions). Those `nn.functional` imports are late (inside the function
that needs them) because importing `paddle_tpu.nn` runs `nn/__init__.py`,
which imports the layers, which import the kernels: a top-level import would
close that cycle. That is allowed here; naming a layer is not.
"""
import ast
import pathlib

import pytest

KERNELS = pathlib.Path(__file__).resolve().parents[1] / 'paddle_tpu' / 'kernels'
MODULES = sorted(p.name for p in KERNELS.glob('*.py') if p.name != '__init__.py')
ABOVE = ('paddle_tpu.nn.layer', 'paddle_tpu.text', 'paddle_tpu.engine',
         'paddle_tpu.serving', 'paddle_tpu.hapi')


def _imported(path):
    """Every module a file imports, absolute, those nested in functions too
    (`from ..nn.layer import x` -> `paddle_tpu.nn.layer` and
    `paddle_tpu.nn.layer.x`: either may be the module)."""
    package = ['paddle_tpu', 'kernels']
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield node.lineno, alias.name
        elif isinstance(node, ast.ImportFrom):
            base = package[:len(package) - node.level + 1] if node.level else []
            base = '.'.join(base + ([node.module] if node.module else []))
            yield node.lineno, base
            for alias in node.names:
                yield node.lineno, base + '.' + alias.name


@pytest.mark.parametrize('module', MODULES)
def test_kernels_import_nothing_above_them(module):
    above = [(line, name) for line, name in _imported(KERNELS / module)
             if any(name == a or name.startswith(a + '.') for a in ABOVE)]
    assert not above, '%s imports a layer above the kernels: %r' % (
        module, above)
