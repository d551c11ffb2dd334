"""Olmo-Hybrid: a dense decoder of Gated DeltaNet layers and full-attention
layers, three to one (`allenai/Olmo-Hybrid-7B`, `model_type` `olmo_hybrid`;
the linear layers are arXiv:2412.06464's, the block the OLMo 2/3 lineage's).

Blocks norm BEHIND each sublayer: `h = x + RMSNorm(mixer(x)); out = h +
RMSNorm(SwiGLU(h))`; a final RMSNorm, an untied head. A linear layer decays
its state by one scalar a head and token and lets `beta` reach 2; a full
layer is plain multi-head causal attention with RMS-normed queries and keys
and no rotation (`rope_theta` null). For training on packed rows: `forward`
takes the token ids, each position's document number and the next-token
labels (-1: no loss there) and returns the mean cross-entropy with the
step's counters; the logits of a whole step are never held at once.

A chip may hold a share of every layer's heads, as the chips of a
head-parallel group do: `heads_held = (first, count)` (docs/HEAD_SHARE.md);
the SwiGLU is whole. `recompute` re-runs each half of a block, with its
norm, in the backward pass: a block keeps its two inputs.
"""
from .. import nn
from ..observability import costs as _costs
from .decoder_block import (STEP_COUNTER_NAMES, STEP_COUNTER_SUMS,
                            PostNormDecoderBlock, merge_counters,
                            packed_head_loss)

# the layers' named scopes and the delta-rule kernels' own (what the decoders
# share is registered by `decoder_block`): a captured step keeps which
# instructions lie under each (observability.costs.scopes)
_costs.register_scopes('gdn.scan', 'gdn.proj', 'attn.full', 'ffn.dense',
                       'delta_rule.pallas', 'short_conv.pallas',
                       'flash_attention.pallas')

__all__ = ['OlmoHybridConfig', 'OlmoHybridBlock', 'OlmoHybridForCausalLM']


class OlmoHybridConfig:
    def __init__(self, vocab_size=100352, hidden_size=3840,
                 num_hidden_layers=32, num_attention_heads=30, head_dim=128,
                 layer_types=None, intermediate_size=11008,
                 linear_num_heads=30, linear_key_head_dim=96,
                 linear_value_head_dim=192, linear_conv_kernel_dim=4,
                 linear_allow_neg_eigval=True, rms_norm_eps=1e-6,
                 initializer_range=0.02, heads_held=None, recompute=False,
                 gdn_chunk=64):
        if layer_types is None:         # every fourth layer is full attention
            layer_types = ['full_attention' if i % 4 == 3
                           else 'linear_attention'
                           for i in range(num_hidden_layers)]
        if linear_num_heads != num_attention_heads:
            raise ValueError('heads_held names one range of heads: the '
                             'linear and the full layers have to count alike')
        self.__dict__.update(
            {k: v for k, v in locals().items() if k != 'self'})


class OlmoHybridBlock(PostNormDecoderBlock):
    """Layer `index` (0-based, as `layer_types` counts)."""

    def __init__(self, config, index):
        c = config
        kind = c.layer_types[index]
        if kind == 'linear_attention':
            mixer = nn.GatedDeltaNet(
                c.hidden_size, c.linear_num_heads, c.linear_key_head_dim,
                c.linear_value_head_dim, conv_kernel=c.linear_conv_kernel_dim,
                allow_neg_eigval=c.linear_allow_neg_eigval,
                heads_held=c.heads_held, epsilon=c.rms_norm_eps,
                chunk=c.gdn_chunk, initializer_range=c.initializer_range)
        elif kind == 'full_attention':
            mixer = nn.CausalSelfAttention(
                c.hidden_size, c.num_attention_heads, c.head_dim,
                heads_held=c.heads_held, epsilon=c.rms_norm_eps,
                initializer_range=c.initializer_range)
        else:
            raise ValueError('layer %d is %r: neither linear_attention nor '
                             'full_attention' % (index, kind))
        super().__init__(c, mixer)


class OlmoHybridForCausalLM(nn.Layer):
    # what the second output of `forward` counts (the decoders share the
    # names; with no expert layer the `moe.*` read zero)
    step_counter_names = STEP_COUNTER_NAMES
    step_counter_sums = STEP_COUNTER_SUMS

    def __init__(self, config=None, **kwargs):
        super().__init__()
        config = config or OlmoHybridConfig(**kwargs)
        self.config = config
        init = nn.ParamAttr(initializer=nn.initializer.Normal(
            0., config.initializer_range))
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size, weight_attr=init)
        self.layers = nn.LayerList([
            OlmoHybridBlock(config, i)
            for i in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size,
                               epsilon=config.rms_norm_eps)
        self.lm_head = self.create_parameter(
            [config.hidden_size, config.vocab_size], attr=init)

    def hidden_states(self, input_ids, segment_ids):
        """-> the last block's output before the final norm."""
        x = self.embed_tokens(input_ids).astype('float32')
        for block in self.layers:
            x = block(x, segment_ids)
        return x

    def forward(self, input_ids, segment_ids, labels):
        x = self.hidden_states(input_ids, segment_ids)
        return (packed_head_loss(self.norm(x), labels, self.lm_head),
                merge_counters([], segment_ids))

    @staticmethod
    def training_loss(loss, counters):
        """The `loss=` of `engine.build_train_step`: `forward` has computed
        it (it takes the labels), the counters ride beside it."""
        return loss
