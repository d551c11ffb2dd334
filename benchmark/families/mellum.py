"""Family `mellum`: causal-LM training of the Mellum 2 decoder (grouped-query
attention, three window layers to one full layer with a rotary table each, a
softmax router over thin experts in every layer) on packed rows, through the
program's engine, as ONE CHIP'S SHARE of an expert-parallel deployment: the
configuration names the experts this chip holds (`experts_held` of
`num_experts_total`) and the rows of the vocabulary it keeps.

What belongs to the family and to no single cell: how the program's net,
loss and optimizer are built from a configuration file, the parameters from
a seed, the host batches from a traffic file, the operations one sample
requires, and which of the optimizer's slots holds the first gradient. The
plain reference is `mellum_reference.py`, beside this file. The rows are
`kimi_linear.make_pool`'s.
"""
# the harness runs with benchmark/ on the path (it is run.py's directory)
from families import kimi_linear as _rows

REFERENCE = 'mellum_reference'


# ------------------------------------------------------------- parameters

def param_spec(cfg):
    """name -> (shape, init). The benchmark's own statement of the
    parameters; `build` holds the program's net to it."""
    H, V = cfg['hidden_size'], cfg['vocab_size']
    std = 'normal:%g' % cfg['initializer_range']
    out = cfg['assumed_values']['output_init']      # what writes to the residual
    heads, kv, d = (cfg['num_attention_heads'], cfg['num_key_value_heads'],
                    cfg['head_dim'])
    F, E = cfg['moe_intermediate_size'], cfg['num_experts']
    spec = {'embed_tokens.weight': ((V, H),
                                    cfg['assumed_values']['embedding_init'])}
    for i in range(cfg['num_hidden_layers']):
        p = 'layers.%d.' % i
        a, m = p + 'attention.', p + 'mlp.'
        spec.update({
            p + 'input_norm.weight': ((H,), 'ones'),
            p + 'post_attention_norm.weight': ((H,), 'ones'),
            a + 'q_proj': ((H, heads * d), std),
            a + 'k_proj': ((H, kv * d), std),
            a + 'v_proj': ((H, kv * d), std),
            a + 'o_proj': ((heads * d, H), out),
            m + 'router': ((H, cfg['num_experts_total']), std),
            m + 'experts_gate': ((E, H, F), std),
            m + 'experts_up': ((E, H, F), std),
            m + 'experts_down': ((E, F, H), out)})
    spec.update({'norm.weight': ((H,), 'ones'), 'lm_head': ((H, V), std)})
    return spec


def buffer_spec(cfg):
    """The softmax router has no correction bias: the net holds no buffer."""
    return {}


# ---------------------------------------------------------------- program

def build(cfg, deterministic=False):
    """The program's (net, loss, optimizer) for this configuration. The net
    has no dropout: `deterministic` changes nothing."""
    from paddle_tpu import optimizer
    from paddle_tpu.text.mellum import MellumConfig, MellumForCausalLM
    lo, hi = cfg['experts_held']
    if hi - lo != cfg['num_experts']:
        raise ValueError('experts_held %r holds %d experts, num_experts says '
                         '%d' % (cfg['experts_held'], hi - lo,
                                 cfg['num_experts']))
    if not cfg['norm_topk_prob'] or cfg['attention_bias'] \
            or cfg['tie_word_embeddings'] or cfg['hidden_act'] != 'silu':
        raise ValueError('the program renormalises the picks, has no '
                         'attention bias, an untied head and SiLU experts')
    n = cfg['num_hidden_layers']
    if set(cfg['mlp_layer_types'][:n]) != {'sparse'}:
        raise ValueError('every layer of the program is sparse')
    net = MellumForCausalLM(MellumConfig(
        vocab_size=cfg['vocab_size'], hidden_size=cfg['hidden_size'],
        num_hidden_layers=n,
        num_attention_heads=cfg['num_attention_heads'],
        num_key_value_heads=cfg['num_key_value_heads'],
        head_dim=cfg['head_dim'], layer_types=cfg['layer_types'][:n],
        sliding_window=cfg['sliding_window'],
        rope_parameters=cfg['rope_parameters'],
        moe_intermediate_size=cfg['moe_intermediate_size'],
        num_experts=cfg['num_experts_total'],
        num_experts_per_token=cfg['num_experts_per_tok'],
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
    every layer, a window where `layer_types` says so; the rotation turns
    the whole head of every query and K/V head; every layer sparse."""
    heads, kv, d = (cfg['num_attention_heads'], cfg['num_key_value_heads'],
                    cfg['head_dim'])
    kinds = cfg['layer_types'][:cfg['num_hidden_layers']]
    return {
        'attention': [
            {'window': cfg['sliding_window']
             if kind == 'sliding_attention' else None,
             'heads': heads, 'kv_heads': kv, 'qk_dim': d, 'v_dim': d}
            for kind in kinds],
        'rotary': [(heads + kv) * d for _ in kinds],
        'experts': {'layers': len(kinds), 'hidden': cfg['hidden_size'],
                    'width': cfg['moe_intermediate_size'],
                    'held': cfg['num_experts'], 'products': 3}}



def flops_per_sample(cfg, traffic):
    """Operations one packed row's forward and backward passes REQUIRE of
    this share: 2 per multiply-add, three passes (forward, and the
    backward's two products), independent of how the program computes them.
    Matrix products with weights (K and V at their own head count); causal
    scores and weighted values inside documents only, a full layer at the
    traffic's EXPECTED pairs a token, a window layer at the expected pairs
    inside documents AND the window (`pairs_per_token_window`, the
    configuration's: the traffic file counts no window); the held experts at
    their expectation, top_k * held / total of a token's picks landing here;
    the head on every position over the vocabulary slice. The rotation,
    recomputation, norms, the router's softmax and top-k and the optimizer do
    not count."""
    L, H, V = traffic['seq_len'], cfg['hidden_size'], cfg['vocab_size']
    heads, kv, d = (cfg['num_attention_heads'], cfg['num_key_value_heads'],
                    cfg['head_dim'])
    pairs = {'full_attention': traffic['expected_pairs_per_token'],
             'sliding_attention':
             cfg['assumed_values']['pairs_per_token_window']}
    share = cfg['num_experts_per_tok'] * cfg['num_experts'] \
        / cfg['num_experts_total']
    moe = H * cfg['num_experts_total'] \
        + 3 * H * cfg['moe_intermediate_size'] * share
    per_token = H * V
    for kind in cfg['layer_types'][:cfg['num_hidden_layers']]:
        per_token += (2 * H * heads * d + 2 * H * kv * d
                      + pairs[kind] * heads * 2 * d + moe)
    return 6.0 * L * per_token
