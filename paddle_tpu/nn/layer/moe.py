"""The feed-forward layers of the sparse decoders: a gated MLP (SwiGLU), an
ungated one (squared ReLU) and one chip's share of a routed expert layer of
either form (`functional.moe`, docs/EXPERT_LAYER.md)."""
import contextlib

import jax
import jax.numpy as jnp

from ...core.tensor import Tensor, apply_op
from ..initializer import Normal, ParamAttr
from ..layer_base import Layer
from ..functional import moe as F_moe
from .linear_attention import compute_dtype, pre_normed

__all__ = ['SwiGLU', 'SquaredReLU', 'SparseMoE']


def _under(scope):
    """The named scope a feed-forward runs under, where it was given one."""
    return jax.named_scope(scope) if scope else contextlib.nullcontext()


class SwiGLU(Layer):
    """down(silu(gate x) * up x), no biases."""

    def __init__(self, hidden_size, intermediate_size,
                 initializer_range=0.02, scope=None):
        super().__init__()
        self.scope = scope

        def weight(*shape):
            return self.create_parameter(list(shape), attr=ParamAttr(
                initializer=Normal(0., initializer_range)))
        self.gate_proj = weight(hidden_size, intermediate_size)
        self.up_proj = weight(hidden_size, intermediate_size)
        self.down_proj = weight(intermediate_size, hidden_size)

    def forward(self, x, pre_norm=None, recompute=False):
        """`pre_norm`: the block's `nn.RMSNorm`, applied to x first;
        `recompute`: both are re-run in the backward pass."""
        dtype, scope = compute_dtype(), self.scope

        def fn(x, gate, up, down):
            with _under(scope):
                return F_moe.swiglu(x, gate, up, down, dtype)
        run, front = pre_normed(fn, pre_norm, recompute)
        return apply_op(run, (x,) + front + (self.gate_proj, self.up_proj,
                                             self.down_proj))


class SquaredReLU(Layer):
    """down(relu(up x)^2), no biases, no gate matrix."""

    def __init__(self, hidden_size, intermediate_size,
                 initializer_range=0.02, scope=None):
        super().__init__()
        self.scope = scope

        def weight(*shape):
            return self.create_parameter(list(shape), attr=ParamAttr(
                initializer=Normal(0., initializer_range)))
        self.up_proj = weight(hidden_size, intermediate_size)
        self.down_proj = weight(intermediate_size, hidden_size)

    def forward(self, x, pre_norm=None, recompute=False):
        """As `SwiGLU.forward`."""
        dtype, scope = compute_dtype(), self.scope

        def fn(x, up, down):
            with _under(scope):
                return F_moe.relu2_mlp(x, up, down, dtype)
        run, front = pre_normed(fn, pre_norm, recompute)
        return apply_op(run, (x,) + front + (self.up_proj, self.down_proj))


class SparseMoE(Layer):
    """One chip's share of an expert layer: a router over all
    `num_experts` in float32, `router='sigmoid'` (top k by score +
    `e_score_correction_bias`, a buffer; weights renormalised and scaled by
    `scaling`) or `router='softmax'` (top k by probability, weights
    renormalised; no bias, no buffer, no scaling); the routed sum over the
    experts `experts_held = (lo, hi)` this chip holds, no token dropped; plus
    the shared expert(s), where there are any, which every chip of the group
    would compute for its own tokens. `activation`: 'silu', experts and
    shared expert `down(silu(gate x) * up x)`, or 'relu2', the ungated
    `down(relu(up x)^2)`, which has no `experts_gate`.
    `block`: rows of a tile of the routed
    product's row buffer (None: worked out from the tokens,
    `functional.moe.row_tile`). `forward`
    returns (y, counters): `functional.moe.COUNTERS`; a list given as
    `selected` is handed each token's picks, sorted."""

    def __init__(self, hidden_size, expert_size, num_experts, top_k,
                 experts_held=None, shared_size=None, scaling=1.0,
                 block=None, initializer_range=0.02, router='sigmoid',
                 activation='silu'):
        super().__init__()
        if router not in ('sigmoid', 'softmax'):
            raise ValueError('no router %r' % (router,))
        if activation not in ('silu', 'relu2'):
            raise ValueError('no expert activation %r' % (activation,))
        if router == 'softmax' and scaling != 1.0:
            raise ValueError('the softmax router scales nothing')
        lo, hi = experts_held or (0, num_experts)
        if not 0 <= lo < hi <= num_experts:
            raise ValueError('experts_held %r is no range of %d experts'
                             % (experts_held, num_experts))
        self.experts_held, self.num_experts = (lo, hi), num_experts
        self.top_k, self.scaling = top_k, scaling
        self.block, self.kind = block, router
        self.gated = activation == 'silu'

        def weight(*shape):
            return self.create_parameter(list(shape), attr=ParamAttr(
                initializer=Normal(0., initializer_range)))
        self.router = weight(hidden_size, num_experts)
        if router == 'sigmoid':
            self.register_buffer(
                'e_score_correction_bias',
                Tensor(jnp.zeros((num_experts,), jnp.float32)))
        held = hi - lo
        if self.gated:
            self.experts_gate = weight(held, hidden_size, expert_size)
        self.experts_up = weight(held, hidden_size, expert_size)
        self.experts_down = weight(held, expert_size, hidden_size)
        self.shared = (SwiGLU if self.gated else SquaredReLU)(
            hidden_size, shared_size, initializer_range,
            scope='moe.shared') if shared_size else None

    def forward(self, x, selected=None, pre_norm=None, recompute=False):
        dtype = compute_dtype()
        held, experts = self.experts_held, self.num_experts
        top_k, scaling, block = self.top_k, self.scaling, self.block
        sigmoid, gated = self.kind == 'sigmoid', self.gated

        def fn(x, router, *rest):
            gate, up, down = rest[-3:] if gated else (None,) + rest[-2:]
            shape = x.shape
            x = x.reshape(-1, shape[-1])
            with jax.named_scope('moe.route'):
                if sigmoid:
                    idx, weights = F_moe.route_sigmoid_topk(
                        x, router, rest[0], top_k, scaling)
                else:
                    idx, weights = F_moe.route_softmax_topk(x, router, top_k)
            with jax.named_scope('moe.experts'):
                y, counters = F_moe.expert_share(
                    x, idx, weights, gate, up, down, held, experts,
                    tile=block, dtype=dtype)
            return (y.reshape(shape), counters,
                    jnp.sort(idx, axis=-1).reshape(shape[:-1] + (top_k,)))

        run, front = pre_normed(fn, pre_norm, recompute)
        y, counters, picks = apply_op(
            run, (x,) + front + (self.router,) + (
                (self.e_score_correction_bias,) if sigmoid else ()) + (
                ((self.experts_gate,) if gated else ())
                + (self.experts_up, self.experts_down)),
            n_outputs=3)
        if selected is not None:
            selected.append(picks)
        if self.shared is not None:
            y = y + self.shared(x, pre_norm, recompute)
        return y, counters
