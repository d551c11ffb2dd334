"""Family `joyai_llm_flash`: causal-LM training of the JoyAI-LLM-Flash decoder
(rotary latent attention with low-rank queries in every layer, a dense SwiGLU
layer then expert layers, a multi-token-prediction module beside the head) on
packed rows, through the program's engine, as ONE CHIP'S SHARE of an
expert-parallel deployment: the configuration names the experts this chip
holds (`experts_held` of `num_experts_total`) and the rows of the vocabulary
it keeps.

What belongs to the family and to no single cell: how the program's net,
loss and optimizer are built from a configuration file, the parameters from
a seed, the host batches from a traffic file, the operations one sample
requires, and which of the optimizer's slots holds the first gradient. The
plain reference is `joyai_llm_flash_reference.py`, beside this file. The
rows are `kimi_linear.make_pool`'s, with a fourth array: the label two ahead.
"""
import numpy as np

# the harness runs with benchmark/ on the path (it is run.py's directory)
from families import kimi_linear as _rows

REFERENCE = 'joyai_llm_flash_reference'


# ------------------------------------------------------------- parameters

def _block_spec(cfg, prefix, sparse):
    H = cfg['hidden_size']
    std = 'normal:%g' % cfg['initializer_range']
    out = cfg['assumed_values']['output_init']      # what writes to the residual
    heads = cfg['num_attention_heads']
    nope, rope = cfg['qk_nope_head_dim'], cfg['qk_rope_head_dim']
    dv, lora, q_lora = (cfg['v_head_dim'], cfg['kv_lora_rank'],
                        cfg['q_lora_rank'])
    F, E = cfg['moe_intermediate_size'], cfg['n_routed_experts']
    a, m = prefix + 'attention.', prefix + 'mlp.'
    spec = {prefix + 'input_norm.weight': ((H,), 'ones'),
            prefix + 'post_attention_norm.weight': ((H,), 'ones'),
            a + 'q_a_proj': ((H, q_lora), std),
            a + 'q_a_norm': ((q_lora,), 'ones'),
            a + 'q_b_proj': ((q_lora, heads * (nope + rope)), std),
            a + 'kv_a_proj': ((H, lora + rope), std),
            a + 'kv_a_norm': ((lora,), 'ones'),
            a + 'kv_b_proj': ((lora, heads * (nope + dv)), std),
            a + 'o_proj': ((heads * dv, H), out)}
    if sparse:
        S = F * cfg['n_shared_experts']
        spec.update({
            m + 'router': ((H, cfg['num_experts_total']), std),
            m + 'experts_gate': ((E, H, F), std),
            m + 'experts_up': ((E, H, F), std),
            m + 'experts_down': ((E, F, H), out),
            m + 'shared.gate_proj': ((H, S), std),
            m + 'shared.up_proj': ((H, S), std),
            m + 'shared.down_proj': ((S, H), out)})
    else:
        I = cfg['intermediate_size']
        spec.update({m + 'gate_proj': ((H, I), std),
                     m + 'up_proj': ((H, I), std),
                     m + 'down_proj': ((I, H), out)})
    return spec


def _blocks(cfg):
    """(leaf prefix, sparse) of every decoder block, the module's last."""
    return [('layers.%d.' % i, i >= cfg['first_k_dense_replace'])
            for i in range(cfg['num_hidden_layers'])] + [('mtp.block.', True)]


def param_spec(cfg):
    """name -> (shape, init). The benchmark's own statement of the
    parameters; `build` holds the program's net to it."""
    H, V = cfg['hidden_size'], cfg['vocab_size']
    std = 'normal:%g' % cfg['initializer_range']
    spec = {'embed_tokens.weight': ((V, H),
                                    cfg['assumed_values']['embedding_init'])}
    for prefix, sparse in _blocks(cfg):
        spec.update(_block_spec(cfg, prefix, sparse))
    spec.update({'norm.weight': ((H,), 'ones'), 'lm_head': ((H, V), std),
                 'mtp.enorm.weight': ((H,), 'ones'),
                 'mtp.hnorm.weight': ((H,), 'ones'),
                 'mtp.eh_proj': ((2 * H, H), std),
                 'mtp.norm.weight': ((H,), 'ones')})
    return spec


def buffer_spec(cfg):
    """The routers' correction biases: zero, as the configuration states."""
    return {prefix + 'mlp.e_score_correction_bias':
            ((cfg['num_experts_total'],), 'zeros')
            for prefix, sparse in _blocks(cfg) if sparse}


# ---------------------------------------------------------------- program

def build(cfg, deterministic=False):
    """The program's (net, loss, optimizer) for this configuration. The net
    has no dropout: `deterministic` changes nothing."""
    from paddle_tpu import optimizer
    from paddle_tpu.text.joyai_flash import (JoyAIFlashConfig,
                                             JoyAIFlashForCausalLM)
    lo, hi = cfg['experts_held']
    if hi - lo != cfg['n_routed_experts']:
        raise ValueError('experts_held %r holds %d experts, n_routed_experts '
                         'says %d' % (cfg['experts_held'], hi - lo,
                                      cfg['n_routed_experts']))
    if cfg['n_group'] != 1 or cfg['topk_group'] != 1:
        raise ValueError('the program has no group-limited routing')
    net = JoyAIFlashForCausalLM(JoyAIFlashConfig(
        vocab_size=cfg['vocab_size'], hidden_size=cfg['hidden_size'],
        num_hidden_layers=cfg['num_hidden_layers'],
        num_attention_heads=cfg['num_attention_heads'],
        intermediate_size=cfg['intermediate_size'],
        moe_intermediate_size=cfg['moe_intermediate_size'],
        num_experts=cfg['num_experts_total'],
        num_experts_per_token=cfg['num_experts_per_tok'],
        num_shared_experts=cfg['n_shared_experts'],
        first_k_dense_replace=cfg['first_k_dense_replace'],
        routed_scaling_factor=cfg['routed_scaling_factor'],
        q_lora_rank=cfg['q_lora_rank'], kv_lora_rank=cfg['kv_lora_rank'],
        qk_nope_head_dim=cfg['qk_nope_head_dim'],
        qk_rope_head_dim=cfg['qk_rope_head_dim'],
        v_head_dim=cfg['v_head_dim'], rope_theta=cfg['rope_theta'],
        num_nextn_predict_layers=cfg['num_nextn_predict_layers'],
        mtp_loss_weight=cfg['assumed_values']['mtp_loss_weight'],
        rms_norm_eps=cfg['rms_norm_eps'],
        initializer_range=cfg['initializer_range'],
        experts_held=(lo, hi), **cfg.get('program', {})))
    net.train()
    o = cfg['optimizer']
    opt = optimizer.AdamW(learning_rate=o['learning_rate'], beta1=o['beta1'],
                          beta2=o['beta2'], epsilon=o['epsilon'],
                          weight_decay=o['weight_decay'])
    return net, net.training_loss, opt


def stochastic(cfg):
    return False


first_gradient = _rows.first_gradient


# ------------------------------------------------------------------- data

def make_pool(cfg, traffic, seed, batches, rows):
    """`kimi_linear.make_pool`'s batches with a fourth array, ((ids, segment
    ids, labels, labels two ahead), ()): a position's second label is the id
    two ahead where that lies in the same document (then so does the id one
    ahead: a document is a run of positions), else -1."""
    out = []
    for (ids, seg, labels), rest in _rows.make_pool(cfg, traffic, seed,
                                                    batches, rows):
        ahead = np.full_like(labels, -1)
        ahead[:, :-2] = np.where(seg[:, 2:] == seg[:, :-2], ids[:, 2:], -1)
        out.append(((ids, seg, labels, ahead), rest))
    return out


augment = _rows.augment
layout_digest = _rows.layout_digest


# ------------------------------------------------------------- operations
def kernel_shapes(cfg):
    """As `kimi_linear.kernel_shapes` says. Here: rotary latent attention at
    192 / 128 in every block, the prediction module's among them; the
    rotation turns the last 64 channels of every query head and the one
    shared 64 of the keys; no convolution and no delta rule."""
    heads, rope = cfg['num_attention_heads'], cfg['qk_rope_head_dim']
    blocks = _blocks(cfg)
    return {
        'attention': [
            {'window': None, 'heads': heads, 'kv_heads': heads,
             'qk_dim': cfg['qk_nope_head_dim'] + rope,
             'v_dim': cfg['v_head_dim']} for _ in blocks],
        'rotary': [(heads + 1) * rope for _ in blocks],
        'experts': {'layers': sum(sparse for _, sparse in blocks),
                    'hidden': cfg['hidden_size'],
                    'width': cfg['moe_intermediate_size'],
                    'held': cfg['n_routed_experts'], 'products': 3}}



def flops_per_sample(cfg, traffic):
    """Operations one packed row's forward and backward passes REQUIRE: 2 per
    multiply-add, three passes (forward, and the backward's two products),
    independent of how the program computes them. Matrix products with
    weights; causal scores and weighted values inside documents only, at the
    traffic's EXPECTED sum of squared document lengths; the routed experts at
    their expectation, top_k * held / total of a token's picks landing here;
    the head on every position TWICE (the prediction module's pass through
    the shared head) and `eh_proj`. The rotation, recomputation, norms, the
    router's top-k and the optimizer do not count."""
    L, H, V = traffic['seq_len'], cfg['hidden_size'], cfg['vocab_size']
    heads = cfg['num_attention_heads']
    nope, rope = cfg['qk_nope_head_dim'], cfg['qk_rope_head_dim']
    dv, lora, q_lora = (cfg['v_head_dim'], cfg['kv_lora_rank'],
                        cfg['q_lora_rank'])
    F = cfg['moe_intermediate_size']
    attention = (H * q_lora + q_lora * heads * (nope + rope)
                 + H * (lora + rope) + lora * heads * (nope + dv)
                 + heads * dv * H
                 # sum over documents of n (n + 1) / 2 pairs, per row, over L
                 + traffic['expected_pairs_per_token'] * heads
                 * (nope + rope + dv))
    dense = 3 * H * cfg['intermediate_size']
    share = cfg['num_experts_per_tok'] * cfg['n_routed_experts'] \
        / cfg['num_experts_total']
    moe = H * cfg['num_experts_total'] + 3 * H * F * (
        cfg['n_shared_experts'] + share)
    per_token = 2 * H * V + 2 * H * H
    for _, sparse in _blocks(cfg):
        per_token += attention + (moe if sparse else dense)
    return 6.0 * L * per_token
