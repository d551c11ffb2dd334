"""Family `olmo_hybrid`: causal-LM training of the Olmo-Hybrid dense decoder
(Gated DeltaNet layers with a decay per head and full-attention layers with
normed queries and keys, three to one, a dense SwiGLU in every layer, the
norm behind each sublayer) on packed rows, through the program's engine, as
ONE CHIP'S SHARE of a head-parallel deployment: the configuration names the
heads of every layer's mixer this chip holds (`heads_held` of
`num_heads_total`) and the rows of the vocabulary it keeps. The SwiGLU is
whole on every chip of the pair.

What belongs to the family and to no single cell: how the program's net,
loss and optimizer are built from a configuration file, the parameters from
a seed, the host batches from a traffic file, the operations one sample
requires, and which of the optimizer's slots holds the first gradient. The
plain reference is `olmo_hybrid_reference.py`, beside this file. The rows
are `kimi_linear.make_pool`'s.
"""
# the harness runs with benchmark/ on the path (it is run.py's directory)
from families import kimi_linear as _rows

REFERENCE = 'olmo_hybrid_reference'

HEAD_COUNTS = ('num_attention_heads', 'num_key_value_heads',
               'linear_num_key_heads', 'linear_num_value_heads')


def _kind(cfg, i):
    """'linear_attention' or 'full_attention' of layer i (0-based)."""
    return cfg['layer_types'][i]


def _held(cfg):
    first, count = cfg['heads_held']
    for key in HEAD_COUNTS:
        if cfg[key] != count:
            raise ValueError('heads_held %r holds %d heads, %s says %d'
                             % (cfg['heads_held'], count, key, cfg[key]))
    if first + count > cfg['num_heads_total']:
        raise ValueError('heads_held %r is no range of %d heads'
                         % (cfg['heads_held'], cfg['num_heads_total']))
    return first, count


# ------------------------------------------------------------- parameters

def param_spec(cfg):
    """name -> (shape, init). The benchmark's own statement of the
    parameters; `build` holds the program's net to it."""
    H, V, I = cfg['hidden_size'], cfg['vocab_size'], cfg['intermediate_size']
    a = cfg['assumed_values']
    std, out = 'normal:%g' % cfg['initializer_range'], a['output_init']
    heads = _held(cfg)[1]
    K, Vd = cfg['linear_key_head_dim'], cfg['linear_value_head_dim']
    D, taps = a['head_dim'], cfg['linear_conv_kernel_dim']
    spec = {'embed_tokens.weight': ((V, H), a['embedding_init'])}
    for i in range(cfg['num_hidden_layers']):
        p = 'layers.%d.' % i
        m = p + 'mixer.'
        if _kind(cfg, i) == 'linear_attention':
            for name, width in (('q', K), ('k', K), ('v', Vd)):
                spec[m + name + '_proj'] = ((H, heads * width), std)
                spec[m + name + '_conv'] = ((taps, heads * width),
                                            a['conv_init'])
            spec[m + 'a_proj'] = ((H, heads), std)
            spec[m + 'b_proj'] = ((H, heads), std)
            spec[m + 'A_log'] = ((heads,), 'zeros')
            spec[m + 'dt_bias'] = ((heads,), a['dt_bias_init'])
            spec[m + 'g_proj'] = ((H, heads * Vd), std)
            spec[m + 'o_norm'] = ((Vd,), 'ones')
            spec[m + 'o_proj'] = ((heads * Vd, H), out)
        else:
            for name in ('q', 'k', 'v'):
                spec[m + name + '_proj'] = ((H, heads * D), std)
            spec[m + 'q_norm'] = ((heads * D,), 'ones')
            spec[m + 'k_norm'] = ((heads * D,), 'ones')
            spec[m + 'o_proj'] = ((heads * D, H), out)
        spec[p + 'post_attention_norm.weight'] = ((H,), 'ones')
        spec[p + 'post_feedforward_norm.weight'] = ((H,), 'ones')
        spec[p + 'mlp.gate_proj'] = ((H, I), std)
        spec[p + 'mlp.up_proj'] = ((H, I), std)
        spec[p + 'mlp.down_proj'] = ((I, H), out)
    spec['norm.weight'] = ((H,), 'ones')
    spec['lm_head'] = ((H, V), std)
    return spec


def buffer_spec(cfg):
    return {}


# ---------------------------------------------------------------- program

def build(cfg, deterministic=False):
    """The program's (net, loss, optimizer) for this configuration. The net
    has no dropout: `deterministic` changes nothing."""
    from paddle_tpu import optimizer
    from paddle_tpu.text.olmo_hybrid import (OlmoHybridConfig,
                                             OlmoHybridForCausalLM)
    if cfg['rope_parameters']['rope_theta'] is not None:
        raise ValueError('the program gives the full-attention layers no '
                         'rotation')
    total, n = cfg['num_heads_total'], cfg['num_hidden_layers']
    net = OlmoHybridForCausalLM(OlmoHybridConfig(
        vocab_size=cfg['vocab_size'], hidden_size=cfg['hidden_size'],
        num_hidden_layers=n, num_attention_heads=total,
        head_dim=cfg['assumed_values']['head_dim'],
        layer_types=cfg['layer_types'][:n],
        intermediate_size=cfg['intermediate_size'], linear_num_heads=total,
        linear_key_head_dim=cfg['linear_key_head_dim'],
        linear_value_head_dim=cfg['linear_value_head_dim'],
        linear_conv_kernel_dim=cfg['linear_conv_kernel_dim'],
        linear_allow_neg_eigval=cfg['linear_allow_neg_eigval'],
        rms_norm_eps=cfg['rms_norm_eps'],
        initializer_range=cfg['initializer_range'],
        heads_held=_held(cfg), **cfg.get('program', {})))
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

make_pool = _rows.make_pool
augment = _rows.augment
layout_digest = _rows.layout_digest


# ------------------------------------------------------------- operations
def kernel_shapes(cfg):
    """As `kimi_linear.kernel_shapes` says, of THIS CHIP'S SHARE. Here: the
    held heads of plain multi-head attention in the full layers, no
    rotation; three convolutions a linear layer (q and k at 96 a head, v at
    192); the delta rule with one decay a head (`gdn.scan_roofline` counts
    it from the configuration's own keys); no experts."""
    heads = _held(cfg)[1]
    K, Vd = cfg['linear_key_head_dim'], cfg['linear_value_head_dim']
    D, taps = cfg['assumed_values']['head_dim'], cfg['linear_conv_kernel_dim']
    kinds = [_kind(cfg, i) for i in range(cfg['num_hidden_layers'])]
    linear = kinds.count('linear_attention')
    return {
        'attention': [
            {'window': None, 'heads': heads, 'kv_heads': heads, 'qk_dim': D,
             'v_dim': D} for kind in kinds if kind == 'full_attention'],
        'short_conv': [
            {'channels': heads * width, 'taps': taps, 'bias': False}
            for _ in range(linear) for width in (K, K, Vd)],
        'delta_rule': {'layers': linear, 'heads': heads, 'key_dim': K,
                       'value_dim': Vd, 'decays': 1}}



def flops_per_sample(cfg, traffic):
    """Operations one packed row's forward and backward passes REQUIRE of
    THIS CHIP'S SHARE, at the published head sizes (96 / 192 / 128, never
    the lanes a kernel lays them on): 2 per multiply-add, three passes
    (forward, and the backward's two products), independent of how the
    program computes them. Matrix products with weights (the held heads'
    columns and rows, the whole SwiGLU, the vocabulary slice); the delta
    rule in its STATE form (per token and head: decay and read the state,
    one rank-one write, one read for the output: 3 d_k d_v multiply-adds);
    causal scores and weighted values inside documents only, at the
    traffic's EXPECTED sum of squared document lengths. Recomputation,
    norms, convolutions, gates' activations and the optimizer do not
    count."""
    L, H, V = traffic['seq_len'], cfg['hidden_size'], cfg['vocab_size']
    heads = _held(cfg)[1]
    K, Vd = cfg['linear_key_head_dim'], cfg['linear_value_head_dim']
    D = cfg['assumed_values']['head_dim']
    linear = H * heads * (2 * K + 3 * Vd + 2) + heads * 3 * K * Vd
    # sum over documents of n (n + 1) / 2 pairs, per row, over L
    full = 4 * H * heads * D \
        + traffic['expected_pairs_per_token'] * heads * 2 * D
    dense = 3 * H * cfg['intermediate_size']
    per_token = H * V
    for i in range(cfg['num_hidden_layers']):
        per_token += dense + (linear if _kind(cfg, i) == 'linear_attention'
                              else full)
    return 6.0 * L * per_token
