"""Family `laguna`: causal-LM training of the Laguna-XS.2 decoder (window and
full grouped-query attention layers at DIFFERENT query-head counts on the
same K/V heads, an output gate a head, a full layer's head of which half
turns, a leading dense layer and then 256 thin experts with a shared one
behind a scaled sigmoid router) on packed rows, through the program's
engine, as ONE CHIP'S SHARE of an expert-parallel deployment: the
configuration names the experts this chip holds (`experts_held` of
`num_experts_total`) and the rows of the vocabulary it keeps.

What belongs to the family and to no single cell: how the program's net,
loss and optimizer are built from a configuration file, the parameters from
a seed, the host batches from a traffic file, the operations one sample
requires, and which of the optimizer's slots holds the first gradient. The
plain reference is `laguna_reference.py`, beside this file. The rows are
`kimi_linear.make_pool`'s.
"""
# the harness runs with benchmark/ on the path (it is run.py's directory)
from families import kimi_linear as _rows

REFERENCE = 'laguna_reference'


def _layers(cfg):
    """(kind, query heads, sparse) of every layer the configuration keeps."""
    n = cfg['num_hidden_layers']
    return list(zip(cfg['layer_types'][:n],
                    cfg['num_attention_heads_per_layer'][:n],
                    (kind == 'sparse' for kind in cfg['mlp_layer_types'][:n])))


def _turned(cfg, kind):
    """The channels of a head that a layer of `kind` turns."""
    return int(cfg['head_dim']
               * cfg['rope_parameters'][kind]['partial_rotary_factor'])


# ------------------------------------------------------------- parameters

def param_spec(cfg):
    """name -> (shape, init). The benchmark's own statement of the
    parameters; `build` holds the program's net to it."""
    H, V = cfg['hidden_size'], cfg['vocab_size']
    std = 'normal:%g' % cfg['initializer_range']
    out = cfg['assumed_values']['output_init']      # what writes to the residual
    kv, d = cfg['num_key_value_heads'], cfg['head_dim']
    F, E = cfg['moe_intermediate_size'], cfg['num_experts']
    S, I = cfg['shared_expert_intermediate_size'], cfg['intermediate_size']
    spec = {'embed_tokens.weight': ((V, H),
                                    cfg['assumed_values']['embedding_init'])}
    for i, (_, heads, sparse) in enumerate(_layers(cfg)):
        p = 'layers.%d.' % i
        a, m = p + 'attention.', p + 'mlp.'
        spec.update({
            p + 'input_norm.weight': ((H,), 'ones'),
            p + 'post_attention_norm.weight': ((H,), 'ones'),
            a + 'q_proj': ((H, heads * d), std),
            a + 'k_proj': ((H, kv * d), std),
            a + 'v_proj': ((H, kv * d), std),
            a + 'o_proj': ((heads * d, H), out),
            a + 'g_proj': ((H, heads), std)})
        if sparse:
            spec.update({
                m + 'router': ((H, cfg['num_experts_total']), std),
                m + 'experts_gate': ((E, H, F), std),
                m + 'experts_up': ((E, H, F), std),
                m + 'experts_down': ((E, F, H), out),
                m + 'shared.gate_proj': ((H, S), std),
                m + 'shared.up_proj': ((H, S), std),
                m + 'shared.down_proj': ((S, H), out)})
        else:
            spec.update({m + 'gate_proj': ((H, I), std),
                         m + 'up_proj': ((H, I), std),
                         m + 'down_proj': ((I, H), out)})
    spec.update({'norm.weight': ((H,), 'ones'), 'lm_head': ((H, V), std)})
    return spec


def buffer_spec(cfg):
    """The routers' correction biases: zero (the configuration has no key
    for one; the program's sigmoid router carries the buffer)."""
    return {'layers.%d.mlp.e_score_correction_bias' % i:
            ((cfg['num_experts_total'],), 'zeros')
            for i, (_, _, sparse) in enumerate(_layers(cfg)) if sparse}


# ---------------------------------------------------------------- program

def build(cfg, deterministic=False):
    """The program's (net, loss, optimizer) for this configuration. The net
    has no dropout: `deterministic` changes nothing."""
    from paddle_tpu import optimizer
    from paddle_tpu.text.laguna import LagunaConfig, LagunaForCausalLM
    lo, hi = cfg['experts_held']
    if hi - lo != cfg['num_experts']:
        raise ValueError('experts_held %r holds %d experts, num_experts says '
                         '%d' % (cfg['experts_held'], hi - lo,
                                 cfg['num_experts']))
    if cfg['attention_bias'] or cfg['tie_word_embeddings'] \
            or not cfg['gating'] or cfg['moe_apply_router_weight_on_input']:
        raise ValueError('the program has no attention bias, an untied head, '
                         'an output gate and router weights on the '
                         "experts' output")
    n = cfg['num_hidden_layers']
    net = LagunaForCausalLM(LagunaConfig(
        vocab_size=cfg['vocab_size'], hidden_size=cfg['hidden_size'],
        num_hidden_layers=n,
        num_attention_heads=cfg['num_attention_heads'],
        num_attention_heads_per_layer=cfg[
            'num_attention_heads_per_layer'][:n],
        num_key_value_heads=cfg['num_key_value_heads'],
        head_dim=cfg['head_dim'], layer_types=cfg['layer_types'][:n],
        mlp_layer_types=cfg['mlp_layer_types'][:n],
        sliding_window=cfg['sliding_window'],
        rope_parameters=cfg['rope_parameters'],
        intermediate_size=cfg['intermediate_size'],
        moe_intermediate_size=cfg['moe_intermediate_size'],
        shared_expert_intermediate_size=cfg[
            'shared_expert_intermediate_size'],
        num_experts=cfg['num_experts_total'],
        num_experts_per_token=cfg['num_experts_per_tok'],
        routed_scaling_factor=cfg['moe_routed_scaling_factor'],
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

make_pool = _rows.make_pool
augment = _rows.augment
layout_digest = _rows.layout_digest


# ------------------------------------------------------------- operations
def kernel_shapes(cfg):
    """As `kimi_linear.kernel_shapes` says. Here: grouped-query attention in
    every layer at the LAYER'S query heads, a window where `layer_types`
    says so; the rotation turns the whole head of every query and K/V head
    in a window layer and half of it in a full layer (the pass still moves
    the whole head: `rope_roofline` counts what turns); every layer but the
    leading dense one sparse."""
    kv, d = cfg['num_key_value_heads'], cfg['head_dim']
    layers = _layers(cfg)
    return {
        'attention': [
            {'window': cfg['sliding_window']
             if kind == 'sliding_attention' else None,
             'heads': heads, 'kv_heads': kv, 'qk_dim': d, 'v_dim': d}
            for kind, heads, _ in layers],
        'rotary': [(heads + kv) * _turned(cfg, kind)
                   for kind, heads, _ in layers],
        'experts': {'layers': sum(sparse for _, _, sparse in layers),
                    'hidden': cfg['hidden_size'],
                    'width': cfg['moe_intermediate_size'],
                    'held': cfg['num_experts'], 'products': 3}}


def flops_per_sample(cfg, traffic):
    """Operations one packed row's forward and backward passes REQUIRE of
    this share: 2 per multiply-add, three passes (forward, and the
    backward's two products), independent of how the program computes them.
    Matrix products with weights (q and o at the layer's query heads, K and
    V at their own head count, the gate's projection); causal scores and
    weighted values inside documents only, a full layer at the traffic's
    EXPECTED pairs a token, a window layer at the expected pairs inside
    documents AND the window (`pairs_per_token_window`, the configuration's:
    the traffic file counts no window); the dense layer's three products;
    in a sparse layer the router, the shared expert on every token and the
    held experts at their expectation, top_k * held / total of a token's
    picks landing here; the head on every position over the vocabulary
    slice. The rotation, the gate's sigmoid and product with the heads,
    recomputation, norms, the router's sigmoid and top-k and the optimizer
    do not count."""
    L, H, V = traffic['seq_len'], cfg['hidden_size'], cfg['vocab_size']
    kv, d = cfg['num_key_value_heads'], cfg['head_dim']
    pairs = {'full_attention': traffic['expected_pairs_per_token'],
             'sliding_attention':
             cfg['assumed_values']['pairs_per_token_window']}
    share = cfg['num_experts_per_tok'] * cfg['num_experts'] \
        / cfg['num_experts_total']
    moe = H * cfg['num_experts_total'] \
        + 3 * H * cfg['shared_expert_intermediate_size'] \
        + 3 * H * cfg['moe_intermediate_size'] * share
    per_token = H * V
    for kind, heads, sparse in _layers(cfg):
        per_token += (2 * H * heads * d + 2 * H * kv * d + H * heads
                      + pairs[kind] * heads * 2 * d
                      + (moe if sparse else 3 * H * cfg['intermediate_size']))
    return 6.0 * L * per_token
