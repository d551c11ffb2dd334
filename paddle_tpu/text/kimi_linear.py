"""Kimi-Linear: a hybrid decoder of Kimi Delta Attention layers and NoPE
latent-attention layers (3:1), a dense SwiGLU layer first and routed expert
layers after (arXiv:2510.26692; `moonshotai/Kimi-Linear-48B-A3B-Instruct`).

Pre-norm residual blocks, `x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x))`, a
final RMSNorm, an untied head. For training on packed rows: `forward` takes
the token ids, each position's document number and the next-token labels
(-1: no loss there) and returns the mean cross-entropy with the expert
layers' counters; the logits of a whole step are never held at once.

A chip may hold a share of the model, as the chips of an expert-parallel
group do: `experts_held` names the experts of each expert layer that live
here (docs/EXPERT_LAYER.md); `recompute` re-runs each half of a block (its
norm with its attention, its norm with its feed-forward) in the backward
pass instead of keeping its activations: a block keeps its two inputs.
"""
from .. import nn
from ..observability import costs as _costs
from .decoder_block import (STEP_COUNTER_NAMES, STEP_COUNTER_SUMS,
                            SparseDecoderBlock, merge_counters,
                            packed_head_loss)

# the KDA layers' named scopes and their kernels' own (the scopes of what the
# sparse decoders share are registered by `decoder_block`): a captured step
# keeps which instructions lie under each (observability.costs.scopes)
_costs.register_scopes('kda.scan', 'kda.proj', 'delta_rule.pallas',
                       'short_conv.pallas')

__all__ = ['KimiLinearConfig', 'KimiLinearBlock', 'KimiLinearForCausalLM']


class KimiLinearConfig:
    def __init__(self, vocab_size=163840, hidden_size=2304,
                 num_hidden_layers=27, num_attention_heads=32, head_dim=128,
                 kda_layers=None, full_attn_layers=None,
                 intermediate_size=9216, moe_intermediate_size=1024,
                 num_experts=256, num_experts_per_token=8,
                 num_shared_experts=1, first_k_dense_replace=1,
                 routed_scaling_factor=2.446, kv_lora_rank=512,
                 qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                 short_conv_kernel_size=4, gate_low_rank=None,
                 rms_norm_eps=1e-5, initializer_range=0.02,
                 experts_held=None, recompute=False, kda_chunk=64,
                 moe_block=None):
        if full_attn_layers is None:        # every fourth layer, 1-based
            full_attn_layers = [i for i in range(1, num_hidden_layers + 1)
                                if i % 4 == 0]
        if kda_layers is None:
            kda_layers = [i for i in range(1, num_hidden_layers + 1)
                          if i not in full_attn_layers]
        self.__dict__.update(
            {k: v for k, v in locals().items() if k != 'self'})


class KimiLinearBlock(SparseDecoderBlock):
    """Layer `index` (1-based) of the decoder -> (x, expert counters)."""

    def __init__(self, config, index):
        c = config
        if index in c.kda_layers:
            attention = nn.KimiDeltaAttention(
                c.hidden_size, c.num_attention_heads, c.head_dim,
                conv_kernel=c.short_conv_kernel_size,
                gate_rank=c.gate_low_rank, epsilon=c.rms_norm_eps,
                chunk=c.kda_chunk, initializer_range=c.initializer_range)
        elif index in c.full_attn_layers:
            attention = nn.LatentAttention(
                c.hidden_size, c.num_attention_heads, c.qk_nope_head_dim,
                c.qk_rope_head_dim, c.v_head_dim, c.kv_lora_rank,
                epsilon=c.rms_norm_eps,
                initializer_range=c.initializer_range)
        else:
            raise ValueError('layer %d is in neither kda_layers nor '
                             'full_attn_layers' % index)
        super().__init__(c, attention, sparse=index > c.first_k_dense_replace)


class KimiLinearForCausalLM(nn.Layer):
    # what the second output of `forward` counts: values of the compiled
    # step, which `engine.TrainStep` records under these names
    step_counter_names = STEP_COUNTER_NAMES
    step_counter_sums = STEP_COUNTER_SUMS

    def __init__(self, config=None, **kwargs):
        super().__init__()
        config = config or KimiLinearConfig(**kwargs)
        self.config = config
        init = nn.ParamAttr(initializer=nn.initializer.Normal(
            0., config.initializer_range))
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size, weight_attr=init)
        self.layers = nn.LayerList([
            KimiLinearBlock(config, i + 1)
            for i in range(config.num_hidden_layers)])
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
        return x, merge_counters(counted, segment_ids)

    def forward(self, input_ids, segment_ids, labels, selected=None):
        x, counters = self.hidden_states(input_ids, segment_ids, selected)
        return packed_head_loss(self.norm(x), labels, self.lm_head), counters

    @staticmethod
    def training_loss(loss, counters):
        """The `loss=` of `engine.build_train_step`: `forward` has computed
        it (it takes the labels), the counters ride beside it."""
        return loss
