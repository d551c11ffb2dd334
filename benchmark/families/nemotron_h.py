"""Family `nemotron_h`: causal-LM training of the Nemotron-H-pattern decoder
(one sublayer a layer: Mamba-2 state-space layers, attention layers without
rotation, ungated relu^2 expert layers with a shared expert) on packed rows,
through the program's engine, as ONE CHIP'S SHARE of an expert-parallel
deployment: the configuration names the experts this chip holds
(`experts_held` of `num_experts_total`) and the rows of the vocabulary it
keeps.

What belongs to the family and to no single cell: how the program's net,
loss and optimizer are built from a configuration file, the parameters from
a seed, the host batches from a traffic file, the operations one sample
requires, and which of the optimizer's slots holds the first gradient. The
plain reference is `nemotron_h_reference.py`, beside this file. The rows are
`kimi_linear.make_pool`'s.
"""
# the harness runs with benchmark/ on the path (it is run.py's directory)
from families import kimi_linear as _rows

REFERENCE = 'nemotron_h_reference'


def _letters(cfg):
    return cfg['hybrid_override_pattern'][:cfg['num_hidden_layers']]


def _widths(cfg):
    """(inner channels, B or C's channels, heads) of a Mamba-2 layer."""
    return (cfg['mamba_num_heads'] * cfg['mamba_head_dim'],
            cfg['n_groups'] * cfg['ssm_state_size'], cfg['mamba_num_heads'])


# ------------------------------------------------------------- parameters

def param_spec(cfg):
    """name -> (shape, init). The benchmark's own statement of the
    parameters; `build` holds the program's net to it."""
    H, V = cfg['hidden_size'], cfg['vocab_size']
    values = cfg['assumed_values']
    std = 'normal:%g' % cfg['initializer_range']
    out = values['output_init']             # what writes to the residual
    inner, bc, mamba_heads = _widths(cfg)
    heads, kv, d = (cfg['num_attention_heads'], cfg['num_key_value_heads'],
                    cfg['head_dim'])
    F, S = (cfg['moe_intermediate_size'],
            cfg['moe_shared_expert_intermediate_size'])
    E = cfg['n_routed_experts']
    spec = {'embed_tokens.weight': ((V, H), values['embedding_init'])}
    for i, letter in enumerate(_letters(cfg)):
        p = 'layers.%d.' % i
        m = p + 'mixer.'
        spec[p + 'norm.weight'] = ((H,), 'ones')
        if letter == 'M':
            spec.update({
                m + 'in_proj': ((H, 2 * inner + 2 * bc + mamba_heads), std),
                m + 'conv_weight': ((cfg['conv_kernel'], inner + 2 * bc),
                                    values['conv_init']),
                m + 'conv_bias': ((inner + 2 * bc,), values['conv_init']),
                m + 'dt_bias': ((mamba_heads,), values['dt_bias_init']),
                m + 'A_log': ((mamba_heads,), values['A_log_init']),
                m + 'D': ((mamba_heads,), 'ones'),
                m + 'norm': ((inner,), 'ones'),
                m + 'out_proj': ((inner, H), out)})
        elif letter == '*':
            spec.update({
                m + 'q_proj': ((H, heads * d), std),
                m + 'k_proj': ((H, kv * d), std),
                m + 'v_proj': ((H, kv * d), std),
                m + 'o_proj': ((heads * d, H), out)})
        elif letter == 'E':
            spec.update({
                m + 'router': ((H, cfg['num_experts_total']), std),
                m + 'experts_up': ((E, H, F), std),
                m + 'experts_down': ((E, F, H), out),
                m + 'shared.up_proj': ((H, S), std),
                m + 'shared.down_proj': ((S, H), out)})
        else:
            raise ValueError('layer %d of the pattern is %r: not M, * or E'
                             % (i, letter))
    spec.update({'norm.weight': ((H,), 'ones'), 'lm_head': ((H, V), std)})
    return spec


def buffer_spec(cfg):
    """The routers' correction biases: zero, as the configuration states."""
    return {'layers.%d.mixer.e_score_correction_bias' % i:
            ((cfg['num_experts_total'],), 'zeros')
            for i, letter in enumerate(_letters(cfg)) if letter == 'E'}


# ---------------------------------------------------------------- program

def build(cfg, deterministic=False):
    """The program's (net, loss, optimizer) for this configuration. The net
    has no dropout: `deterministic` changes nothing."""
    from paddle_tpu import optimizer
    from paddle_tpu.text.nemotron_h import (NemotronHConfig,
                                            NemotronHForCausalLM)
    lo, hi = cfg['experts_held']
    if hi - lo != cfg['n_routed_experts']:
        raise ValueError('experts_held %r holds %d experts, n_routed_experts '
                         'says %d' % (cfg['experts_held'], hi - lo,
                                      cfg['n_routed_experts']))
    if not cfg['norm_topk_prob'] or cfg['attention_bias'] \
            or cfg['mamba_proj_bias'] or cfg['mlp_bias'] \
            or cfg['tie_word_embeddings'] or cfg['n_shared_experts'] != 1 \
            or cfg['n_group'] != 1 or cfg['topk_group'] != 1 \
            or cfg['mamba_hidden_act'] != 'silu':
        raise ValueError('the program renormalises the picks, limits them '
                         'to no group, has no bias in a projection, an '
                         'untied head, one shared expert and SiLU in its '
                         'state-space layers')
    net = NemotronHForCausalLM(NemotronHConfig(
        vocab_size=cfg['vocab_size'], hidden_size=cfg['hidden_size'],
        num_hidden_layers=cfg['num_hidden_layers'],
        hybrid_override_pattern=cfg['hybrid_override_pattern'],
        mamba_num_heads=cfg['mamba_num_heads'],
        mamba_head_dim=cfg['mamba_head_dim'], n_groups=cfg['n_groups'],
        ssm_state_size=cfg['ssm_state_size'],
        conv_kernel=cfg['conv_kernel'], use_conv_bias=cfg['use_conv_bias'],
        chunk_size=cfg['chunk_size'],
        num_attention_heads=cfg['num_attention_heads'],
        num_key_value_heads=cfg['num_key_value_heads'],
        head_dim=cfg['head_dim'],
        moe_intermediate_size=cfg['moe_intermediate_size'],
        moe_shared_expert_intermediate_size=cfg[
            'moe_shared_expert_intermediate_size'],
        n_routed_experts=cfg['num_experts_total'],
        num_experts_per_tok=cfg['num_experts_per_tok'],
        routed_scaling_factor=cfg['routed_scaling_factor'],
        mlp_hidden_act=cfg['mlp_hidden_act'],
        rms_norm_eps=cfg['layer_norm_epsilon'],
        initializer_range=cfg['initializer_range'],
        time_step_min=cfg['time_step_min'],
        time_step_max=cfg['time_step_max'],
        time_step_floor=cfg['time_step_floor'],
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

make_pool = _rows.make_pool
augment = _rows.augment
layout_digest = _rows.layout_digest


# ------------------------------------------------------------- operations
def kernel_shapes(cfg):
    """As `kimi_linear.kernel_shapes` says. Here: 32 query heads on 2 K/V
    heads in a `*` layer, no rotation; an `M` layer's convolution with its
    bias over x, B and C (the program makes three calls of it, 4096 + 1024
    + 1024 channels); ungated experts (up, down) at the published 1856; the
    state-space rule is `ssd.scan_roofline`'s, from the configuration's own
    keys."""
    inner, bc, _ = _widths(cfg)
    letters = _letters(cfg)
    return {
        'attention': [
            {'window': None, 'heads': cfg['num_attention_heads'],
             'kv_heads': cfg['num_key_value_heads'],
             'qk_dim': cfg['head_dim'], 'v_dim': cfg['head_dim']}
            for letter in letters if letter == '*'],
        'short_conv': [
            {'channels': width, 'taps': cfg['conv_kernel'],
             'bias': cfg['use_conv_bias']}
            for letter in letters if letter == 'M'
            for width in (inner, bc, bc)],
        'experts': {'layers': letters.count('E'),
                    'hidden': cfg['hidden_size'],
                    'width': cfg['moe_intermediate_size'],
                    'held': cfg['n_routed_experts'], 'products': 2}}



def flops_per_sample(cfg, traffic):
    """Operations one packed row's forward and backward passes REQUIRE of
    this share: 2 per multiply-add, three passes (forward, and the
    backward's two products), independent of how the program computes them.
    Matrix products with weights (K and V at their own head count). A
    Mamba-2 layer's rule in its chunk form at the published `chunk_size` Q
    (per token: a group's C B^T, Q x N; a head's masked product with x,
    Q x P; its state's write and its read, P x N each), which is what any
    implementation that trains on rows of thousands of tokens computes.
    Causal scores and weighted values inside documents only, at the
    traffic's EXPECTED pairs a token. The shared expert on every token, the
    held experts at their expectation, top_k * held / total of a token's
    picks landing here; the head on every position over the vocabulary
    slice. Recomputation, norms, convolutions, activations, the router's
    top-k and the optimizer do not count."""
    L, H, V = traffic['seq_len'], cfg['hidden_size'], cfg['vocab_size']
    inner, bc, mamba_heads = _widths(cfg)
    Q, P, N = (cfg['chunk_size'], cfg['mamba_head_dim'],
               cfg['ssm_state_size'])
    heads, kv, d = (cfg['num_attention_heads'], cfg['num_key_value_heads'],
                    cfg['head_dim'])
    share = cfg['num_experts_per_tok'] * cfg['n_routed_experts'] \
        / cfg['num_experts_total']
    per_layer = {
        'M': H * (2 * inner + 2 * bc + mamba_heads) + inner * H
        + cfg['n_groups'] * Q * N + mamba_heads * (Q * P + 2 * P * N),
        '*': 2 * H * heads * d + 2 * H * kv * d
        + traffic['expected_pairs_per_token'] * heads * 2 * d,
        'E': H * cfg['num_experts_total']
        + 2 * H * cfg['moe_shared_expert_intermediate_size']
        + 2 * H * cfg['moe_intermediate_size'] * share}
    per_token = H * V + sum(per_layer[letter] for letter in _letters(cfg))
    return 6.0 * L * per_token
