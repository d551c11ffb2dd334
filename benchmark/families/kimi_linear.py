"""Family `kimi_linear`: causal-LM training of the Kimi-Linear hybrid decoder
(KDA and NoPE latent attention, a dense SwiGLU layer then expert layers) on
packed rows, through the program's engine, as ONE CHIP'S SHARE of an
expert-parallel deployment: the configuration names the experts this chip
holds (`experts_held` of `num_experts_total`) and the rows of the vocabulary
it keeps.

What belongs to the family and to no single cell: how the program's net,
loss and optimizer are built from a configuration file, the parameters from
a seed, the host batches from a traffic file, the operations one sample
requires, and which of the optimizer's slots holds the first gradient. The
plain reference is `kimi_linear_reference.py`, beside this file.
"""
import hashlib

import numpy as np

REFERENCE = 'kimi_linear_reference'


def _lin(cfg):
    return cfg['linear_attn_config']


def _kinds(cfg, i):
    """(attention, feed-forward) of layer i (0-based)."""
    return ('kda' if i + 1 in _lin(cfg)['kda_layers'] else 'mla',
            'dense' if i < cfg['first_k_dense_replace'] else 'moe')


# ------------------------------------------------------------- parameters

def param_spec(cfg):
    """name -> (shape, init). The benchmark's own statement of the
    parameters; `build` holds the program's net to it."""
    H, V = cfg['hidden_size'], cfg['vocab_size']
    std = 'normal:%g' % cfg['initializer_range']
    out = cfg['assumed_values']['output_init']      # what writes to the residual
    kh, kd = _lin(cfg)['num_heads'], _lin(cfg)['head_dim']
    inner, taps = kh * kd, _lin(cfg)['short_conv_kernel_size']
    rank = cfg['assumed_values']['gate_low_rank']
    heads = cfg['num_attention_heads']
    nope, rope = cfg['qk_nope_head_dim'], cfg['qk_rope_head_dim']
    dv, lora = cfg['v_head_dim'], cfg['kv_lora_rank']
    F, E = cfg['moe_intermediate_size'], cfg['num_experts']
    spec = {'embed_tokens.weight': ((V, H),
                                    cfg['assumed_values']['embedding_init'])}
    for i in range(cfg['num_hidden_layers']):
        p = 'layers.%d.' % i
        attention, ffn = _kinds(cfg, i)
        spec[p + 'input_norm.weight'] = ((H,), 'ones')
        spec[p + 'post_attention_norm.weight'] = ((H,), 'ones')
        a = p + 'attention.'
        if attention == 'kda':
            for name in ('q', 'k', 'v'):
                spec[a + name + '_proj'] = ((H, inner), std)
                spec[a + name + '_conv'] = ((taps, inner),
                                            cfg['assumed_values']['conv_init'])
            spec[a + 'decay_a'] = ((H, rank), std)
            spec[a + 'decay_b'] = ((rank, inner), std)
            spec[a + 'A_log'] = ((kh,), 'zeros')
            spec[a + 'dt_bias'] = ((inner,),
                                   cfg['assumed_values']['dt_bias_init'])
            spec[a + 'beta_proj'] = ((H, kh), std)
            spec[a + 'gate_a'] = ((H, rank), std)
            spec[a + 'gate_b'] = ((rank, inner), std)
            spec[a + 'o_norm'] = ((kd,), 'ones')
            spec[a + 'o_proj'] = ((inner, H), out)
        else:
            spec[a + 'q_proj'] = ((H, heads * (nope + rope)), std)
            spec[a + 'kv_a_proj'] = ((H, lora + rope), std)
            spec[a + 'kv_a_norm'] = ((lora,), 'ones')
            spec[a + 'kv_b_proj'] = ((lora, heads * (nope + dv)), std)
            spec[a + 'o_proj'] = ((heads * dv, H), out)
        m = p + 'mlp.'
        if ffn == 'dense':
            I = cfg['intermediate_size']
            spec[m + 'gate_proj'] = ((H, I), std)
            spec[m + 'up_proj'] = ((H, I), std)
            spec[m + 'down_proj'] = ((I, H), out)
        else:
            S = F * cfg['num_shared_experts']
            spec[m + 'router'] = ((H, cfg['num_experts_total']), std)
            spec[m + 'experts_gate'] = ((E, H, F), std)
            spec[m + 'experts_up'] = ((E, H, F), std)
            spec[m + 'experts_down'] = ((E, F, H), out)
            spec[m + 'shared.gate_proj'] = ((H, S), std)
            spec[m + 'shared.up_proj'] = ((H, S), std)
            spec[m + 'shared.down_proj'] = ((S, H), out)
    spec['norm.weight'] = ((H,), 'ones')
    spec['lm_head'] = ((H, V), std)
    return spec


def buffer_spec(cfg):
    """The routers' correction biases: zero, as the configuration states."""
    return {'layers.%d.mlp.e_score_correction_bias' % i:
            ((cfg['num_experts_total'],), 'zeros')
            for i in range(cfg['num_hidden_layers'])
            if _kinds(cfg, i)[1] == 'moe'}


# ---------------------------------------------------------------- program

def build(cfg, deterministic=False):
    """The program's (net, loss, optimizer) for this configuration. The net
    has no dropout: `deterministic` changes nothing."""
    from paddle_tpu import optimizer
    from paddle_tpu.text.kimi_linear import (KimiLinearConfig,
                                             KimiLinearForCausalLM)
    lo, hi = cfg['experts_held']
    if hi - lo != cfg['num_experts']:
        raise ValueError('experts_held %r holds %d experts, num_experts says '
                         '%d' % (cfg['experts_held'], hi - lo,
                                 cfg['num_experts']))
    lin = _lin(cfg)
    n = cfg['num_hidden_layers']
    net = KimiLinearForCausalLM(KimiLinearConfig(
        vocab_size=cfg['vocab_size'], hidden_size=cfg['hidden_size'],
        num_hidden_layers=n,
        num_attention_heads=cfg['num_attention_heads'],
        head_dim=lin['head_dim'],
        kda_layers=[i for i in lin['kda_layers'] if i <= n],
        full_attn_layers=[i for i in lin['full_attn_layers'] if i <= n],
        intermediate_size=cfg['intermediate_size'],
        moe_intermediate_size=cfg['moe_intermediate_size'],
        num_experts=cfg['num_experts_total'],
        num_experts_per_token=cfg['num_experts_per_token'],
        num_shared_experts=cfg['num_shared_experts'],
        first_k_dense_replace=cfg['first_k_dense_replace'],
        routed_scaling_factor=cfg['routed_scaling_factor'],
        kv_lora_rank=cfg['kv_lora_rank'],
        qk_nope_head_dim=cfg['qk_nope_head_dim'],
        qk_rope_head_dim=cfg['qk_rope_head_dim'],
        v_head_dim=cfg['v_head_dim'],
        short_conv_kernel_size=lin['short_conv_kernel_size'],
        gate_low_rank=cfg['assumed_values']['gate_low_rank'],
        rms_norm_eps=cfg['rms_norm_eps'],
        initializer_range=cfg['initializer_range'],
        experts_held=(lo, hi), **cfg.get('program', {})))
    if lin['num_heads'] != cfg['num_attention_heads']:
        raise ValueError('the program gives KDA and MLA one head count')
    net.train()
    o = cfg['optimizer']
    opt = optimizer.AdamW(learning_rate=o['learning_rate'], beta1=o['beta1'],
                          beta2=o['beta2'], epsilon=o['epsilon'],
                          weight_decay=o['weight_decay'])
    return net, net.training_loss, opt


def stochastic(cfg):
    return False


def first_gradient(cfg, slots, start):
    """The gradient AdamW was handed on its first step, from a parameter's
    slots after that step: moment1 = (1 - beta1) * g."""
    return slots['moment1'] / (1.0 - cfg['optimizer']['beta1'])


# ------------------------------------------------------------------- data

def _lengths(rs, traffic, seq):
    """Document lengths that fill a row exactly: log-normal, clipped, the
    last cut to fit."""
    out, left = [], seq
    lo, hi = traffic['doc_len_clip']
    while left > 0:
        n = int(np.clip(np.rint(rs.lognormal(
            np.log(traffic['doc_len_median']), traffic['doc_len_sigma'])),
            lo, hi))
        out.append(min(n, left))
        left -= out[-1]
    return out


def make_pool(cfg, traffic, seed, batches, rows):
    """`batches` host batches of `rows` packed rows, ((ids, segment ids,
    labels), ()). A row is filled exactly with documents, so nothing is
    padding. The documents' lengths are the TRAFFIC's (`layout_seed`): the
    same rows of documents in every run, because the flash kernels' time
    follows a row's documents and two runs are to time the same work. What
    fills them is the run's: inside a document the next id is, with
    probability `copy_prob`, a fixed seeded permutation of the current one
    (which a model can learn), else uniform in the vocabulary slice. A
    position's label is the next id where that lies in the same document,
    else -1."""
    rs = np.random.default_rng([int(seed), 0x4B1A])
    layout = np.random.default_rng([int(traffic['layout_seed']), 0x4B1A])
    seq, V = traffic['seq_len'], cfg['vocab_size']
    successor = rs.permutation(V).astype(np.int32)
    n = batches * rows
    ids = rs.integers(0, V, (n, seq)).astype(np.int32)
    copy = rs.random((n, seq)) < traffic['copy_prob']
    seg = np.zeros((n, seq), np.int32)
    for r in range(n):
        lengths = _lengths(layout, traffic, seq)
        seg[r] = np.repeat(np.arange(len(lengths)), lengths)
    inside = np.concatenate([np.zeros((n, 1), bool),
                             seg[:, 1:] == seg[:, :-1]], axis=1)
    copy &= inside
    for t in range(1, seq):         # a chain: each id may follow the last
        ids[:, t] = np.where(copy[:, t], successor[ids[:, t - 1]], ids[:, t])
    labels = np.full((n, seq), -1, np.int32)
    labels[:, :-1] = np.where(inside[:, 1:], ids[:, 1:], -1)
    out = []
    for b in range(batches):
        s = slice(b * rows, (b + 1) * rows)
        out.append(((ids[s], seg[s], labels[s]), ()))
    return out


def layout_digest(pool):
    """One digest of the pool's document layouts, whatever their order."""
    each = sorted(hashlib.sha256(np.ascontiguousarray(batch[0][1]).tobytes())
                  .hexdigest() for batch in pool)
    return hashlib.sha256(''.join(each).encode()).hexdigest()[:16]


def augment(traffic, batch, rs):
    """Nothing is done to a text batch on its way to the device."""
    return batch


# ---------------------------------------------------------------- kernels

def kernel_shapes(cfg):
    """What one step asks of each kernel family, at the published sizes and
    in no kernel's own terms: what the `*_roofline` readers under
    `layer_metrics/` count operations and bytes from. Keys, each present
    only where the model has the mechanism:

    `attention`   one entry a softmax-attention layer the step runs:
                  `window` (keys a query sees, None: all of its document),
                  `heads`, `kv_heads`, `qk_dim`, `v_dim`
    `rotary`      one entry a layer that turns q and k: the channels turned
                  a token, q's and k's together
    `short_conv`  one entry a call: `channels`, `taps`, `bias`
    `experts`     `layers`, `hidden`, `width`, `held`, `products` (3 gated:
                  gate, up, down; 2 ungated)
    `delta_rule`  `layers`, `heads`, `key_dim`, `value_dim`, `decays` (a
                  token and head: `key_dim` where every channel has its own)

    Here: the NoPE latent layers at 192 / 128 on every head (the kernels
    are handed k with the shared 64 broadcast); three convolutions a KDA
    layer over all 4096 channels; the delta rule with a decay a channel."""
    lin = _lin(cfg)
    kinds = [_kinds(cfg, i) for i in range(cfg['num_hidden_layers'])]
    kda = sum(a == 'kda' for a, _ in kinds)
    heads = cfg['num_attention_heads']
    inner = lin['num_heads'] * lin['head_dim']
    return {
        'attention': [
            {'window': None, 'heads': heads, 'kv_heads': heads,
             'qk_dim': cfg['qk_nope_head_dim'] + cfg['qk_rope_head_dim'],
             'v_dim': cfg['v_head_dim']}
            for a, _ in kinds if a == 'mla'],
        'short_conv': [
            {'channels': inner, 'taps': lin['short_conv_kernel_size'],
             'bias': False} for _ in range(3 * kda)],
        'experts': {'layers': sum(f == 'moe' for _, f in kinds),
                    'hidden': cfg['hidden_size'],
                    'width': cfg['moe_intermediate_size'],
                    'held': cfg['num_experts'], 'products': 3},
        'delta_rule': {'layers': kda, 'heads': lin['num_heads'],
                       'key_dim': lin['head_dim'],
                       'value_dim': lin['head_dim'],
                       'decays': lin['head_dim']}}


# ------------------------------------------------------------- operations

def flops_per_sample(cfg, traffic):
    """Operations one packed row's forward and backward passes REQUIRE: 2 per
    multiply-add, three passes (forward, and the backward's two products),
    independent of how the program computes them. Matrix products with
    weights; the delta rule in its STATE form (per token and head: decay and
    read the state, one rank-one write, one read for the output: 3 d_k d_v
    multiply-adds); causal scores and weighted values inside documents only,
    at the traffic's EXPECTED sum of squared document lengths; the routed
    experts at their expectation, top_k * held / total of a token's picks
    landing here; the head on every position. Recomputation, norms,
    convolutions, gates' activations, the router's top-k and the optimizer do
    not count."""
    L, H, V = traffic['seq_len'], cfg['hidden_size'], cfg['vocab_size']
    lin = _lin(cfg)
    kh, kd = lin['num_heads'], lin['head_dim']
    inner, rank = kh * kd, cfg['assumed_values']['gate_low_rank']
    heads = cfg['num_attention_heads']
    nope, rope = cfg['qk_nope_head_dim'], cfg['qk_rope_head_dim']
    dv, lora = cfg['v_head_dim'], cfg['kv_lora_rank']
    F = cfg['moe_intermediate_size']
    kda = (4 * H * inner + 2 * (H * rank + rank * inner) + H * kh
           + kh * 3 * kd * kd)
    mla_weights = (H * heads * (nope + rope) + H * (lora + rope)
                   + lora * heads * (nope + dv) + heads * dv * H)
    # sum over documents of n (n + 1) / 2 pairs, per row, over L
    pairs_per_token = traffic['expected_pairs_per_token']
    mla = mla_weights + pairs_per_token * heads * (nope + rope + dv)
    dense = 3 * H * cfg['intermediate_size']
    share = cfg['num_experts_per_token'] * cfg['num_experts'] \
        / cfg['num_experts_total']
    moe = H * cfg['num_experts_total'] + 3 * H * F * (
        cfg['num_shared_experts'] + share)
    per_token = H * V
    for i in range(cfg['num_hidden_layers']):
        attention, ffn = _kinds(cfg, i)
        per_token += kda if attention == 'kda' else mla
        per_token += dense if ffn == 'dense' else moe
    return 6.0 * L * per_token
