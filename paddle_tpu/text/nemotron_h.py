"""Nemotron-H: a decoder whose every layer is ONE sublayer, a Mamba-2
state-space layer, an attention layer or an expert layer by the letter of a
pattern (`model_type` `nemotron_h`, arXiv:2504.03624; the tower that
`nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16`'s config.json declares).
For training on packed rows (x: a row, `seg` its document numbers):

- Layer i: `x += mixer_i(RMSNorm(x))`, `hybrid_override_pattern[i]` naming
  the mixer; a final RMSNorm; an untied head.
- `M`, Mamba-2 (`nn.Mamba2`): heads of `mamba_head_dim` channels sharing B
  and C by group, a convolution with a bias over x, B and C, the state-space
  rule chunk-wise (`kernels.ssd`), the gate before the grouped norm.
- `*`, attention (`nn.GroupedQueryAttention(inv_freq=None)`): grouped-query
  heads, no bias and NO rotation: the state-space layers carry the order.
- `E`, experts (`nn.SparseMoE`, `router='sigmoid'`, `activation='relu2'`):
  sigmoid scores with a correction bias, the top k renormalised and scaled;
  ungated experts `down(relu(up x)^2)` and one shared expert of the same
  form.
- `-`, a dense feed-forward layer, is refused: no configuration here has one.

`forward` takes the ids, each position's document number and the next-token
labels (-1: no loss there) and returns the loss with the step's counters: the
EXPERT layers' (`moe.*`, the other layers have none) and the tile pairs one
attention layer's forward kernel visits per head on the step's rows, with
the document bounds and without. `experts_held` and `recompute` are
`text/kimi_linear.py`'s: a chip may hold a share of each expert layer
(docs/EXPERT_LAYER.md), and a layer keeps its input and is re-run in the
backward pass.
"""
from .. import nn
from ..observability import costs as _costs
from .decoder_block import (STEP_COUNTER_NAMES, STEP_COUNTER_SUMS,
                            SingleMixerBlock, merge_counters,
                            packed_head_loss)

# the state-space layers' scopes and the kernels' own (what the decoders
# share is registered by `decoder_block`): a captured step keeps which
# instructions lie under each (observability.costs.scopes)
_costs.register_scopes('ssm.proj', 'ssm.conv', 'ssm.scan', 'ssd.pallas',
                       'attn.full', 'short_conv.pallas',
                       'flash_attention.pallas')

__all__ = ['NemotronHConfig', 'NemotronHBlock', 'NemotronHForCausalLM',
           'layer_kinds']

KINDS = {'M': 'mamba', '*': 'attention', 'E': 'experts'}


def layer_kinds(pattern, layers=None):
    """`hybrid_override_pattern` -> the kind of each of its first `layers`
    letters (all of them where None)."""
    letters = pattern if layers is None else pattern[:layers]
    if layers is not None and len(letters) < layers:
        raise ValueError('the pattern %r names %d layers, not %d'
                         % (pattern, len(pattern), layers))
    for i, letter in enumerate(letters):
        if letter == '-':
            raise ValueError(
                "layer %d of the pattern %r is '-', a dense feed-forward "
                'layer: none is built (the configurations here have none)'
                % (i, pattern))
        if letter not in KINDS:
            raise ValueError('layer %d of the pattern %r is %r: not M '
                             '(Mamba-2), * (attention) or E (experts)'
                             % (i, pattern, letter))
    return [KINDS[letter] for letter in letters]


class NemotronHConfig:
    def __init__(self, vocab_size=131072, hidden_size=2688,
                 num_hidden_layers=52,
                 hybrid_override_pattern='MEMEM*EMEMEM*EMEMEM*EMEMEM*'
                 'EMEMEM*EMEMEMEM*EMEMEMEME',
                 mamba_num_heads=64, mamba_head_dim=64, n_groups=8,
                 ssm_state_size=128, conv_kernel=4, use_conv_bias=True,
                 chunk_size=128, num_attention_heads=32,
                 num_key_value_heads=2, head_dim=128,
                 moe_intermediate_size=1856,
                 moe_shared_expert_intermediate_size=3712,
                 n_routed_experts=128, num_experts_per_tok=6,
                 routed_scaling_factor=2.5, mlp_hidden_act='relu2',
                 rms_norm_eps=1e-5, initializer_range=0.02,
                 time_step_min=0.001, time_step_max=0.1,
                 time_step_floor=1e-4, experts_held=None, recompute=False,
                 moe_block=None):
        if mlp_hidden_act != 'relu2':
            raise ValueError('the experts are down(relu(up x)^2): no '
                             'mlp_hidden_act %r' % (mlp_hidden_act,))
        self.__dict__.update(
            {k: v for k, v in locals().items() if k != 'self'})
        self.layer_kinds = layer_kinds(hybrid_override_pattern,
                                       num_hidden_layers)


class NemotronHBlock(SingleMixerBlock):
    """Layer `index` (0-based) of the decoder -> (x, expert counters)."""

    def __init__(self, config, index):
        c = config
        kind = c.layer_kinds[index]
        if kind == 'mamba':
            mixer = nn.Mamba2(
                c.hidden_size, c.mamba_num_heads, c.mamba_head_dim,
                c.n_groups, c.ssm_state_size, conv_kernel=c.conv_kernel,
                conv_bias=c.use_conv_bias, chunk=c.chunk_size,
                epsilon=c.rms_norm_eps,
                initializer_range=c.initializer_range,
                dt_min=c.time_step_min, dt_max=c.time_step_max,
                dt_floor=c.time_step_floor)
        elif kind == 'attention':
            mixer = nn.GroupedQueryAttention(
                c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
                c.head_dim, inv_freq=None,
                initializer_range=c.initializer_range)
        else:
            mixer = nn.SparseMoE(
                c.hidden_size, c.moe_intermediate_size, c.n_routed_experts,
                c.num_experts_per_tok, experts_held=c.experts_held,
                shared_size=c.moe_shared_expert_intermediate_size,
                scaling=c.routed_scaling_factor, block=c.moe_block,
                initializer_range=c.initializer_range, router='sigmoid',
                activation=c.mlp_hidden_act)
        super().__init__(c, mixer, sparse=kind == 'experts')


class NemotronHForCausalLM(nn.Layer):
    # what the second output of `forward` counts: values of the compiled
    # step, which `engine.TrainStep` records under these names
    step_counter_names = STEP_COUNTER_NAMES
    step_counter_sums = STEP_COUNTER_SUMS

    def __init__(self, config=None, **kwargs):
        super().__init__()
        config = config or NemotronHConfig(**kwargs)
        self.config = config
        init = nn.ParamAttr(initializer=nn.initializer.Normal(
            0., config.initializer_range))
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size, weight_attr=init)
        self.layers = nn.LayerList([
            NemotronHBlock(config, i)
            for i in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size,
                               epsilon=config.rms_norm_eps)
        self.lm_head = self.create_parameter(
            [config.hidden_size, config.vocab_size], attr=init)

    def hidden_states(self, input_ids, segment_ids, selected=None):
        """-> (the last layer's output before the final norm, counters). A
        list given as `selected` gets each expert layer's picks (B, T, k),
        sorted."""
        x = self.embed_tokens(input_ids).astype('float32')
        counted = []
        for block in self.layers:
            x, counters = block(x, segment_ids, selected)
            if block.sparse:
                counted.append(counters)
        return x, merge_counters(counted, segment_ids)

    def forward(self, input_ids, segment_ids, labels, selected=None):
        x, counters = self.hidden_states(input_ids, segment_ids, selected)
        return packed_head_loss(self.norm(x), labels, self.lm_head), counters

    @staticmethod
    def training_loss(loss, counters):
        """The `loss=` of `engine.build_train_step`: `forward` has computed
        it (it takes the labels), the counters ride beside it."""
        return loss
