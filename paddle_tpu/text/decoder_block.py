"""What the decoders (`kimi_linear`, `joyai_flash`, `olmo_hybrid`, `mellum`,
`nemotron_h`) share: the pre-norm residual block with a dense or a routed
feed-forward, the OLMo 2/3 block that norms BEHIND each sublayer, the block
of ONE sublayer, the next-token loss of a packed row taken a row at a time,
and the step's counters (the expert layers' and the flash kernels' tile
pairs) as one vector. The attention layer is the caller's choice.
"""
import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor, apply_op
from ..kernels.flash_attention import doc_tile_counts
from ..nn.functional.moe import COUNTERS, swiglu
from ..nn.layer.linear_attention import (compute_dtype, doc_starts,
                                         post_normed)
from ..observability import costs as _costs

# the shared layers' named scopes, the kernels' own and the engine's optimizer
# update, which a step of this size spends whole milliseconds in: a captured
# step keeps which instructions lie under each (observability.costs.scopes),
# for the device time a layer takes
_costs.register_scopes('mla.attention', 'moe.route', 'moe.experts',
                       'moe.shared', 'ffn.dense', 'lm_head',
                       'fused_rms_norm.pallas',
                       'grouped_matmul.pallas', 'row_permute.pallas',
                       'update')

__all__ = ['SparseDecoderBlock', 'PostNormDecoderBlock',
           'SingleMixerBlock', 'packed_head_loss',
           'merge_counters', 'STEP_COUNTER_NAMES', 'STEP_COUNTER_SUMS']

# what a sparse decoder's `forward` returns beside its loss, as
# `engine.TrainStep` records it (the net's `step_counter_names`), and the
# names among them that add up over steps (`step_counter_sums`): the expert
# layers' counters, then the tile pairs ONE latent layer's forward kernel
# visits per head on the step's rows, with the document bounds and without
# (every latent layer of a step sees the same rows)
FLASH_COUNTERS = ('flash.tiles_swept', 'flash.tiles_causal')
STEP_COUNTER_NAMES = tuple('moe.' + name for name in COUNTERS) \
    + FLASH_COUNTERS
STEP_COUNTER_SUMS = ('moe.assignments_held', 'moe.assignments',
                     'moe.dropped', 'moe.rows_computed',
                     'moe.rounds', 'moe.rows_moved') + FLASH_COUNTERS


class SparseDecoderBlock(nn.Layer):
    """`x += attention(RMSNorm(x)); x += FFN(RMSNorm(x))` -> (x, expert
    counters). `attention(x, segment_ids, pre_norm, recompute)` is any layer
    of `nn.layer.linear_attention`; the feed-forward is the expert layer
    where `sparse`, else SwiGLU under the scope `ffn.dense`. `config` names
    the sizes (`hidden_size`, `intermediate_size`, `moe_intermediate_size`,
    `num_experts`, `num_experts_per_token`, `num_shared_experts`,
    `routed_scaling_factor`, `experts_held`, `moe_block`, `rms_norm_eps`,
    `initializer_range`; `router`, 'sigmoid' where the configuration names
    none; `shared_expert_intermediate_size`, the shared expert's own width,
    `moe_intermediate_size * num_shared_experts` where it names none) and
    `recompute`: each half norms inside its own traced function, which is
    then re-run in the backward pass, so the block keeps its two inputs."""

    def __init__(self, config, attention, sparse):
        super().__init__()
        c = config
        self.input_norm = nn.RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps)
        self.post_attention_norm = nn.RMSNorm(c.hidden_size,
                                              epsilon=c.rms_norm_eps)
        self.attention = attention
        self.sparse = sparse
        self.recompute = c.recompute
        if self.sparse:
            shared = getattr(c, 'shared_expert_intermediate_size', None)
            if shared is None:
                shared = c.moe_intermediate_size * c.num_shared_experts
            self.mlp = nn.SparseMoE(
                c.hidden_size, c.moe_intermediate_size, c.num_experts,
                c.num_experts_per_token, experts_held=c.experts_held,
                shared_size=shared,
                scaling=c.routed_scaling_factor, block=c.moe_block,
                initializer_range=c.initializer_range,
                router=getattr(c, 'router', 'sigmoid'))
        else:
            self.mlp = nn.SwiGLU(c.hidden_size, c.intermediate_size,
                                 c.initializer_range, scope='ffn.dense')

    def forward(self, x, segment_ids, selected=None):
        again = self.recompute
        x = x + self.attention(x, segment_ids, self.input_norm, again) \
            .astype('float32')
        if self.sparse:
            y, counters = self.mlp(x, selected, self.post_attention_norm,
                                   again)
        else:
            y = self.mlp(x, self.post_attention_norm, again)
            counters = Tensor(jnp.zeros((len(COUNTERS),), jnp.float32))
        return x + y.astype('float32'), counters


class PostNormDecoderBlock(nn.Layer):
    """`h = x + RMSNorm(mixer(x)); out = h + RMSNorm(SwiGLU(h))`: the OLMo
    2/3 block. `mixer(x, segment_ids, post_norm, recompute)` is
    `nn.GatedDeltaNet` or `nn.CausalSelfAttention`, which may hold a share of
    its heads (what it returns, and so what is normed and added, is then
    that share's addend: docs/HEAD_SHARE.md); the SwiGLU is whole, under the
    scope `ffn.dense`. `config` names `hidden_size`, `intermediate_size`,
    `rms_norm_eps`, `initializer_range` and `recompute`: each half, with its
    norm, is re-run in the backward pass, so the block keeps its two
    inputs."""

    def __init__(self, config, mixer):
        super().__init__()
        c = config
        self.mixer = mixer
        self.post_attention_norm = nn.RMSNorm(c.hidden_size,
                                              epsilon=c.rms_norm_eps)
        self.post_feedforward_norm = nn.RMSNorm(c.hidden_size,
                                                epsilon=c.rms_norm_eps)
        self.mlp = nn.SwiGLU(c.hidden_size, c.intermediate_size,
                             c.initializer_range)
        self.recompute = c.recompute

    def forward(self, x, segment_ids):
        x = x + self.mixer(x, segment_ids, self.post_attention_norm,
                           self.recompute).astype('float32')
        dtype = compute_dtype()

        def ffn(x, gate, up, down):
            with jax.named_scope('ffn.dense'):
                return swiglu(x, gate, up, down, dtype)
        run, behind = post_normed(ffn, self.post_feedforward_norm,
                                  self.recompute)
        y = apply_op(run, (x,) + behind + (
            self.mlp.gate_proj, self.mlp.up_proj, self.mlp.down_proj))
        return x + y.astype('float32')


class SingleMixerBlock(nn.Layer):
    """`x += mixer(RMSNorm(x))`: a layer of ONE sublayer (the Nemotron-H
    decoders') -> (x, expert counters). `mixer` is a layer that takes
    `(x, segment_ids, pre_norm, recompute)`, or, where `sparse`, an
    `nn.SparseMoE`, whose counters the block hands on (zeros otherwise).
    `config` names `hidden_size`, `rms_norm_eps` and `recompute`: the mixer
    norms inside its own traced function, which is then re-run in the
    backward pass, so the block keeps its one input."""

    def __init__(self, config, mixer, sparse=False):
        super().__init__()
        self.norm = nn.RMSNorm(config.hidden_size,
                               epsilon=config.rms_norm_eps)
        self.mixer, self.sparse = mixer, sparse
        self.recompute = config.recompute

    def forward(self, x, segment_ids, selected=None):
        if self.sparse:
            y, counters = self.mixer(x, selected, self.norm, self.recompute)
        else:
            y = self.mixer(x, segment_ids, self.norm, self.recompute)
            counters = Tensor(jnp.zeros((len(COUNTERS),), jnp.float32))
        return x + y.astype('float32'), counters


def packed_head_loss(x, labels, head):
    """Mean cross-entropy of `x @ head` (x already normed) against `labels`
    over the positions whose label is not -1, under the scope `lm_head`: a
    row at a time, recomputed in the backward pass, so a step's logits
    (tokens x vocabulary, float32) are never held at once."""
    dtype = compute_dtype()

    def loss_fn(x, labels, head):
        @jax.checkpoint
        def row(x, labels):
            xx, hh = (x, head) if dtype is None else (
                x.astype(dtype), head.astype(dtype))
            logits = jnp.matmul(xx, hh,
                                preferred_element_type=jnp.float32)
            picked = jnp.take_along_axis(
                logits, jnp.maximum(labels, 0)[:, None], axis=-1)[:, 0]
            nll = jax.nn.logsumexp(logits, axis=-1) - picked
            return jnp.sum(jnp.where(labels >= 0, nll, 0.0))
        with jax.named_scope('lm_head'):
            total = jnp.sum(jax.lax.map(lambda a: row(*a), (x, labels)))
            return total / jnp.maximum(jnp.sum(labels >= 0), 1)

    return apply_op(loss_fn, (x, labels, head))


def merge_counters(counted, segment_ids):
    """A step's counters as one vector in STEP_COUNTER_NAMES' order. The
    expert layers': sums, but the busiest expert's rows and the mean rows,
    which are those of the layer where their ratio (the load imbalance) is
    largest. The flash kernels': from the rows' documents, once a step."""
    at = {name: i for i, name in enumerate(COUNTERS)}

    def fn(seg, *cs):
        moe = jnp.zeros((len(COUNTERS),), jnp.float32)
        if cs:
            c = jnp.stack(cs)
            top, mean = (c[:, at['expert_rows_max']],
                         c[:, at['expert_rows_mean']])
            worst = c[jnp.argmax(top / jnp.maximum(mean, 1e-9))]
            moe = jnp.stack([
                worst[i] if name in ('expert_rows_max', 'expert_rows_mean')
                else jnp.sum(c[:, i]) for i, name in enumerate(COUNTERS)])
        return jnp.concatenate(
            [moe, jnp.stack(doc_tile_counts(doc_starts(seg)))])
    return apply_op(fn, (segment_ids,) + tuple(counted),
                    differentiable=False)
