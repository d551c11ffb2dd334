"""Mellum 2: a decoder of grouped-query attention layers, three with an
attention window to one without, every layer's feed-forward a routed expert
layer (`JetBrains/Mellum2-12B-A2.5B-Instruct`, `model_type` `mellum`). For
training on packed rows (x: a row, `seg` its document numbers, p_t = t minus
the start of t's document):

- Block: `x += Attn(RMSNorm(x)); x += MoE(RMSNorm(x))` in every layer; a
  final RMSNorm; an untied head.
- Attention (`nn.GroupedQueryAttention`): q = x W_q (`num_attention_heads`
  heads of `head_dim`), k = x W_k, v = x W_v (`num_key_value_heads`), no
  bias; query head h reads K/V head h // group; q and k rotated over the
  whole head in the half-split form, x cos + [-x2, x1] sin, by p_t times the
  layer kind's inverse frequencies; softmax(q k^T / sqrt(head_dim)) inside
  documents; W_o. `layer_types[i]`:
  'sliding_attention': the plain table theta^(-2j / head_dim), and a query
  sees the last `sliding_window` keys of its document, itself among them;
  'full_attention': every key of its document, the YaRN table
  (`kernels.rotary.yarn_inv_freq`) and cos and sin times
  `attention_factor`.
- Experts (`nn.SparseMoE`, `router='softmax'`): s = softmax(x W_r) over all
  `num_experts` in float32, the `num_experts_per_token` largest, weights
  renormalised over the picks; no shared expert, no bias, no scaling factor,
  no auxiliary loss.

`forward` takes the ids, each position's document number and the next-token
labels (-1: no loss there) and returns the loss with the step's counters:
the expert layers' (`moe.*`), the tile pairs a FULL layer's forward kernel
visits per head on the step's rows with the document bounds and without
(`flash.tiles_swept`, `flash.tiles_causal`, as the other decoders count
them), and those a WINDOW layer's visits (`flash.window_tiles_swept`).
`experts_held`, `recompute` and `moe_block` are `text/kimi_linear.py`'s: a
chip may hold a share of each expert layer (docs/EXPERT_LAYER.md), and a
block keeps its two inputs and re-runs each half in the backward pass.

The decoder is also that of every `config.json` of this layout
(`layer_types`, `mlp_layer_types`, `rope_parameters` by layer kind,
`sliding_window`): what such a model adds to Mellum 2 the configuration
names and `MellumBlock` reads, each None or Mellum's value here: the query
heads by layer (`num_attention_heads_per_layer`), a dense SwiGLU where
`mlp_layer_types[i]` is 'dense', an output gate a head (`gate`), a layer
kind's `partial_rotary_factor`, a shared expert of its own width and the
sigmoid router with its factor (`text/laguna.py`).
"""
import jax.numpy as jnp

from .. import nn
from ..core.tensor import apply_op
from ..kernels.flash_attention import doc_tile_counts
from ..nn.layer.linear_attention import (doc_starts, rope_inv_freq,
                                         yarn_inv_freq)
from ..observability import costs as _costs
from .decoder_block import (STEP_COUNTER_NAMES, STEP_COUNTER_SUMS,
                            SparseDecoderBlock, merge_counters,
                            packed_head_loss)

# the window layers' scope and the rotation inside either kind (`attn.full`
# is the dense hybrid's too; a scope is registered once whoever names it)
_costs.register_scopes('attn.full', 'attn.window', 'attn.rope',
                       'flash_attention.pallas', 'rotary.pallas')

__all__ = ['MellumConfig', 'MellumBlock', 'MellumForCausalLM']

WINDOW_COUNTER = 'flash.window_tiles_swept'


class MellumConfig:
    # what `SparseDecoderBlock` reads and this model has none of
    num_shared_experts, routed_scaling_factor, router = 0, 1.0, 'softmax'
    shared_expert_intermediate_size = None
    intermediate_size = mlp_layer_types = None      # every layer is sparse
    # what `MellumBlock` reads and this model has none of
    num_attention_heads_per_layer = gate = None

    def __init__(self, vocab_size=98304, hidden_size=2304,
                 num_hidden_layers=28, num_attention_heads=32,
                 num_key_value_heads=4, head_dim=128, layer_types=None,
                 sliding_window=1024, rope_parameters=None,
                 moe_intermediate_size=896, num_experts=64,
                 num_experts_per_token=8, rms_norm_eps=1e-6,
                 initializer_range=0.02, experts_held=None, recompute=False,
                 moe_block=None):
        if layer_types is None:     # every fourth layer sees its whole document
            layer_types = ['full_attention' if i % 4 == 3
                           else 'sliding_attention'
                           for i in range(num_hidden_layers)]
        if rope_parameters is None:
            rope_parameters = {
                'sliding_attention': {'rope_type': 'default',
                                      'rope_theta': 500000},
                'full_attention': {
                    'rope_type': 'yarn', 'rope_theta': 500000, 'factor': 16,
                    'original_max_position_embeddings': 8192,
                    'beta_fast': 32, 'beta_slow': 1,
                    'attention_factor': 1.2772588722239782}}
        self.__dict__.update(
            {k: v for k, v in locals().items() if k != 'self'})


def rotary_table(parameters, dim):
    """One layer kind's `rope_parameters` -> (inverse frequencies (dim / 2,),
    the factor on cos and sin), `dim` the channels of a head that turn."""
    kind, theta = parameters['rope_type'], parameters['rope_theta']
    if kind == 'default':
        return rope_inv_freq(theta, dim), 1.0
    if kind != 'yarn':
        raise ValueError('no rotary table of type %r' % (kind,))
    table, _, _ = yarn_inv_freq(
        theta, dim, parameters['factor'],
        parameters['original_max_position_embeddings'],
        parameters['beta_fast'], parameters['beta_slow'])
    return table, parameters['attention_factor']


class MellumBlock(SparseDecoderBlock):
    """Layer `index` (0-based) of the decoder -> (x, expert counters)."""

    def __init__(self, config, index):
        c = config
        kind = c.layer_types[index]
        if kind not in ('sliding_attention', 'full_attention'):
            raise ValueError('layer %d is of no known type: %r'
                             % (index, kind))
        rope = c.rope_parameters[kind]
        turned = int(c.head_dim * rope.get('partial_rotary_factor', 1))
        inv_freq, factor = rotary_table(rope, turned)
        window = c.sliding_window if kind == 'sliding_attention' else None
        heads = c.num_attention_heads if not c.num_attention_heads_per_layer \
            else c.num_attention_heads_per_layer[index]
        super().__init__(c, nn.GroupedQueryAttention(
            c.hidden_size, heads, c.num_key_value_heads, c.head_dim,
            inv_freq, rope_factor=factor, window=window,
            initializer_range=c.initializer_range, rotary_dim=turned,
            gate=c.gate),
            sparse=not c.mlp_layer_types
            or c.mlp_layer_types[index] == 'sparse')


class MellumForCausalLM(nn.Layer):
    # what the second output of `forward` counts: values of the compiled
    # step, which `engine.TrainStep` records under these names
    step_counter_names = STEP_COUNTER_NAMES + (WINDOW_COUNTER,)
    step_counter_sums = STEP_COUNTER_SUMS + (WINDOW_COUNTER,)
    config_class = MellumConfig

    def __init__(self, config=None, **kwargs):
        super().__init__()
        config = config or self.config_class(**kwargs)
        self.config = config
        init = nn.ParamAttr(initializer=nn.initializer.Normal(
            0., config.initializer_range))
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size, weight_attr=init)
        self.layers = nn.LayerList([
            MellumBlock(config, i) for i in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size,
                               epsilon=config.rms_norm_eps)
        self.lm_head = self.create_parameter(
            [config.hidden_size, config.vocab_size], attr=init)

    def hidden_states(self, input_ids, segment_ids, selected=None):
        """-> (the last block's output before the final norm, counters). A
        list given as `selected` gets each expert layer's picks (B, T, k),
        sorted."""
        x = self.embed_tokens(input_ids).astype('float32')
        counted = []
        for block in self.layers:
            x, counters = block(x, segment_ids, selected)
            if block.sparse:
                counted.append(counters)
        window = self.config.sliding_window
        counters = apply_op(
            lambda c, seg: jnp.concatenate([c, doc_tile_counts(
                doc_starts(seg), window=window)[0][None]]),
            (merge_counters(counted, segment_ids), segment_ids),
            differentiable=False)
        return x, counters

    def forward(self, input_ids, segment_ids, labels, selected=None):
        x, counters = self.hidden_states(input_ids, segment_ids, selected)
        return packed_head_loss(self.norm(x), labels, self.lm_head), counters

    @staticmethod
    def training_loss(loss, counters):
        """The `loss=` of `engine.build_train_step`: `forward` has computed
        it (it takes the labels), the counters ride beside it."""
        return loss
