"""JoyAI-LLM-Flash: a decoder of the DeepSeek-V3 lineage (arXiv:2412.19437
sections 2.1-2.2; `jdopensource/JoyAI-LLM-Flash`): rotary multi-head latent
attention with low-rank queries in EVERY layer, a dense SwiGLU layer first and
routed expert layers after, and a multi-token-prediction module that trains
beside the head. For training on packed rows (x: a row, `seg` its document
numbers, p_t = t minus the start of t's document):

- Block: `x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x))`; the first
  `first_k_dense_replace` layers' FFN is SwiGLU, the others' the expert layer
  (`nn.SparseMoE`); a final RMSNorm; an untied head.
- Attention (`nn.LatentAttention` with `q_lora_rank` and `rope_theta`):
  c_q = RMSNorm(x W_qa); [q_nope, q_pe] = c_q W_qb per head;
  [c_kv, k_pe] = x W_kva, c_kv = RMSNorm(c_kv); [k_nope, v] = c_kv W_kvb per
  head; q = [q_nope, R(p_t) q_pe], k = [k_nope, R(p_t) k_pe] with the ONE
  rotated k_pe shared by the heads; R(p) turns each adjacent pair
  (2j, 2j + 1) by p * theta^(-2j / d_rope), in float32; causal
  softmax(q k^T / sqrt(d_nope + d_rope)) inside documents; W_o. No bias.
- Prediction module (`num_nextn_predict_layers` 1), h_t the main model's
  output at t after its final norm:
  h'_t = W_eh [RMSNorm_e(Emb(id_{t+1})) ; RMSNorm_h(h_t)] (the embedding
  first, as the family's released checkpoints lay `eh_proj` out; the paper
  prints the other order, a permutation of W_eh's rows);
  g = Block_mtp(h'), an expert-layer block of its own with the same `seg` and
  positions; logits'_t = RMSNorm_mtp(g_t) W_head with the SHARED head and Emb
  the SHARED table; L_mtp = mean cross-entropy of logits'_t against id_{t+2}
  over the positions whose t+1 and t+2 lie in t's document.
- L = L_main + `mtp_loss_weight` L_mtp.

`forward` takes the ids, each position's document number, the next-token
labels and the labels two ahead (-1: no loss there) and returns the loss with
the counters: the expert layers' (`moe.*`, over every expert layer, the
module's too) and the two mean losses before the weighted sum, `loss.main`
and `loss.mtp`. `experts_held`, `recompute` and `moe_block` are
`text/kimi_linear.py`'s: a chip may hold a share of each expert layer, and a
block keeps its two inputs and re-runs each half in the backward pass.
"""
import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import apply_op
from ..nn.functional.norm import rms_norm_values
from ..nn.layer.linear_attention import compute_dtype
from ..observability import costs as _costs
from .decoder_block import (STEP_COUNTER_NAMES, STEP_COUNTER_SUMS,
                            SparseDecoderBlock, merge_counters,
                            packed_head_loss)

# the rotation inside `mla.attention`, and everything the prediction module
# runs: a scope is a path component, so the module's own attention counts
# under `mtp`, `mla.attention` and `mla.rope` alike
_costs.register_scopes('mla.rope', 'mtp', 'rotary.pallas')

__all__ = ['JoyAIFlashConfig', 'JoyAIFlashForCausalLM']


class JoyAIFlashConfig:
    def __init__(self, vocab_size=129280, hidden_size=2048,
                 num_hidden_layers=40, num_attention_heads=32,
                 intermediate_size=7168, moe_intermediate_size=768,
                 num_experts=256, num_experts_per_token=8,
                 num_shared_experts=1, first_k_dense_replace=1,
                 routed_scaling_factor=2.5, q_lora_rank=1536,
                 kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
                 v_head_dim=128, rope_theta=32e6, num_nextn_predict_layers=1,
                 mtp_loss_weight=0.3, rms_norm_eps=1e-6,
                 initializer_range=0.02, experts_held=None, recompute=False,
                 moe_block=None):
        if num_nextn_predict_layers != 1:
            raise ValueError('the model trains ONE prediction module, not %r'
                             % num_nextn_predict_layers)
        self.__dict__.update(
            {k: v for k, v in locals().items() if k != 'self'})


def _block(config, sparse):
    c = config
    return SparseDecoderBlock(c, nn.LatentAttention(
        c.hidden_size, c.num_attention_heads, c.qk_nope_head_dim,
        c.qk_rope_head_dim, c.v_head_dim, c.kv_lora_rank,
        epsilon=c.rms_norm_eps, initializer_range=c.initializer_range,
        q_lora_rank=c.q_lora_rank, rope_theta=c.rope_theta), sparse)


class PredictionModule(nn.Layer):
    """One multi-token-prediction depth: (h, Emb(ids one ahead), seg) ->
    (RMSNorm_mtp(Block_mtp(W_eh [RMSNorm_e(e) ; RMSNorm_h(h)])), the block's
    expert counters). Embedding and head are the model's."""

    def __init__(self, config):
        super().__init__()
        c = config
        self.enorm = nn.RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps)
        self.hnorm = nn.RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps)
        self.eh_proj = self.create_parameter(
            [2 * c.hidden_size, c.hidden_size], attr=nn.ParamAttr(
                initializer=nn.initializer.Normal(0., c.initializer_range)))
        self.block = _block(c, sparse=True)
        self.norm = nn.RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps)
        self.recompute = c.recompute

    def forward(self, h, e, segment_ids, selected=None):
        dtype, eps = compute_dtype(), self.enorm._epsilon

        def joined(e, h, we, wh, w):
            both = jnp.concatenate([rms_norm_values(e, we, eps),
                                    rms_norm_values(h, wh, eps)], axis=-1)
            if dtype is not None:
                both, w = both.astype(dtype), w.astype(dtype)
            return jnp.matmul(both, w).astype(jnp.float32)
        # the two normed copies and their 2H-wide join are re-made in the
        # backward pass, as a block's halves are
        x = apply_op(jax.checkpoint(joined) if self.recompute else joined,
                     (e, h, self.enorm.weight, self.hnorm.weight,
                      self.eh_proj))
        x, counters = self.block(x, segment_ids, selected)
        return self.norm(x), counters


class JoyAIFlashForCausalLM(nn.Layer):
    # what the second output of `forward` counts: values of the compiled
    # step, which `engine.TrainStep` records under these names
    step_counter_names = STEP_COUNTER_NAMES + ('loss.main', 'loss.mtp')
    step_counter_sums = STEP_COUNTER_SUMS

    def __init__(self, config=None, **kwargs):
        super().__init__()
        config = config or JoyAIFlashConfig(**kwargs)
        self.config = config
        init = nn.ParamAttr(initializer=nn.initializer.Normal(
            0., config.initializer_range))
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size, weight_attr=init)
        self.layers = nn.LayerList([
            _block(config, sparse=i >= config.first_k_dense_replace)
            for i in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size,
                               epsilon=config.rms_norm_eps)
        self.lm_head = self.create_parameter(
            [config.hidden_size, config.vocab_size], attr=init)
        self.mtp = PredictionModule(config)

    def forward(self, input_ids, segment_ids, labels, labels_ahead,
                selected=None):
        """-> (L_main + mtp_loss_weight L_mtp, counters). A list given as
        `selected` gets each expert layer's picks (B, T, k), sorted, the
        module's last."""
        x = self.embed_tokens(input_ids).astype('float32')
        counted = []
        for block in self.layers:
            x, counters = block(x, segment_ids, selected)
            if block.sparse:
                counted.append(counters)
        h = self.norm(x)
        main = packed_head_loss(h, labels, self.lm_head)
        with jax.named_scope('mtp'):
            # the id one ahead; what the row's last position is given (the
            # wrapped first id) meets no label
            ids_ahead = apply_op(lambda ids: jnp.roll(ids, -1, axis=1),
                                 (input_ids,), differentiable=False)
            g, counters = self.mtp(
                h, self.embed_tokens(ids_ahead).astype('float32'),
                segment_ids, selected)
            counted.append(counters)
            ahead = packed_head_loss(g, labels_ahead, self.lm_head)
        loss = main + self.config.mtp_loss_weight * ahead
        counters = apply_op(
            lambda c, a, b: jnp.concatenate([c, jnp.stack([a, b])]),
            (merge_counters(counted, segment_ids), main, ahead),
            differentiable=False)
        return loss, counters

    @staticmethod
    def training_loss(loss, counters):
        """The `loss=` of `engine.build_train_step`: `forward` has computed
        it (it takes the labels), the counters ride beside it."""
        return loss
