"""Laguna: Mellum's decoder (`text/mellum.py`: window and full grouped-query
attention layers by `layer_types`, a rotary table a kind, routed experts)
with what `poolside/Laguna-XS.2` (`model_type` `laguna`) adds to it, each a
key of its `config.json` that `MellumBlock` reads:

- `num_attention_heads_per_layer`: the two kinds of layer differ in their
  QUERY heads (48 in a full layer, 64 in a window layer) over the same 8
  K/V heads of 128, so in their group (6 and 8) and their q and o widths.
- `gating`: one scalar a query head and token on the heads' way into W_o,
  o_h <- sigmoid(RMSNorm(x) W_g)_h o_h (`nn.GroupedQueryAttention(gate=
  'per_head')`, the scope `attn.gate`).
- `rope_parameters[kind].partial_rotary_factor`: a full layer turns the
  first 64 of a head's 128 channels, in the half-split form INSIDE those 64
  (channel j < 32 pairs with j + 32), by the YaRN table of dimension 64, cos
  and sin times `attention_factor`; channels 64-127 pass. A window layer
  turns the whole head by the plain table.
- `mlp_layer_types`: layer 0's feed-forward is a dense SwiGLU
  (`intermediate_size`, the scope `ffn.dense`), every other layer's the
  expert layer with `router='sigmoid'`: s = sigmoid(x W_r) over all
  `num_experts` in float32, the `num_experts_per_token` largest by s + bias
  (a zero buffer), weights s renormalised over the picks times
  `routed_scaling_factor`, on the experts' output; plus one shared SwiGLU
  expert of `shared_expert_intermediate_size` for every token.

Nothing else differs: the block, the loss, the counters, `experts_held`,
`recompute` and `moe_block` are `text/mellum.py`'s.
"""
from ..observability import costs as _costs
from .mellum import MellumConfig, MellumForCausalLM

_costs.register_scopes('attn.gate')

__all__ = ['LagunaConfig', 'LagunaForCausalLM']


class LagunaConfig(MellumConfig):
    router = 'sigmoid'

    def __init__(self, vocab_size=100352, hidden_size=2048,
                 num_hidden_layers=40, num_attention_heads=48,
                 num_attention_heads_per_layer=None, num_key_value_heads=8,
                 head_dim=128, layer_types=None, mlp_layer_types=None,
                 sliding_window=512, rope_parameters=None,
                 intermediate_size=8192, moe_intermediate_size=512,
                 shared_expert_intermediate_size=512, num_experts=256,
                 num_experts_per_token=8, routed_scaling_factor=2.5,
                 gate='per_head', rms_norm_eps=1e-6, initializer_range=0.02,
                 experts_held=None, recompute=False, moe_block=None):
        if layer_types is None:     # every fourth layer, the first among them
            layer_types = ['sliding_attention' if i % 4
                           else 'full_attention'
                           for i in range(num_hidden_layers)]
        if num_attention_heads_per_layer is None:
            num_attention_heads_per_layer = [
                64 if kind == 'sliding_attention' else num_attention_heads
                for kind in layer_types]
        if mlp_layer_types is None:
            mlp_layer_types = ['dense'] + ['sparse'] * (num_hidden_layers - 1)
        if rope_parameters is None:
            rope_parameters = {
                'sliding_attention': {'rope_type': 'default',
                                      'rope_theta': 10000,
                                      'partial_rotary_factor': 1},
                'full_attention': {
                    'rope_type': 'yarn', 'rope_theta': 500000, 'factor': 64,
                    'original_max_position_embeddings': 4096,
                    'beta_fast': 64, 'beta_slow': 1,
                    'attention_factor': 1.4158883083359672,
                    'partial_rotary_factor': 0.5}}
        self.__dict__.update(
            {k: v for k, v in locals().items() if k != 'self'})


class LagunaForCausalLM(MellumForCausalLM):
    config_class = LagunaConfig
