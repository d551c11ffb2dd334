"""Family `bert`: BERT pre-training (MLM + NSP) through the program's engine.

What belongs to the family and to no single cell: how the program's net,
loss and optimizer are built from a configuration file, the parameters from
a seed, the host batches from a traffic file, the operations one sample
requires, and which of the optimizer's slots holds the first gradient. The
plain reference is `bert_reference.py`, beside this file.
"""
import numpy as np

REFERENCE = 'bert_reference'


# ------------------------------------------------------------- parameters

def param_spec(cfg):
    """name -> (shape, init). The benchmark's own statement of the
    parameters; `build` holds the program's net to it."""
    H, F, V = cfg['hidden_size'], cfg['intermediate_size'], cfg['vocab_size']
    std = 'normal:%g' % cfg['initializer_range']
    spec = {
        'bert.embeddings.word_embeddings.weight': ((V, H), std),
        'bert.embeddings.position_embeddings.weight':
            ((cfg['max_position_embeddings'], H), std),
        'bert.embeddings.token_type_embeddings.weight':
            ((cfg['type_vocab_size'], H), std),
        'bert.embeddings.layer_norm.weight': ((H,), 'ones'),
        'bert.embeddings.layer_norm.bias': ((H,), 'zeros'),
    }
    for i in range(cfg['num_hidden_layers']):
        p = 'bert.encoder.layers.%d.' % i
        for proj in ('q_proj', 'k_proj', 'v_proj', 'out_proj'):
            spec[p + 'self_attn.%s.weight' % proj] = ((H, H), std)
            spec[p + 'self_attn.%s.bias' % proj] = ((H,), 'zeros')
        spec[p + 'linear1.weight'] = ((H, F), std)
        spec[p + 'linear1.bias'] = ((F,), 'zeros')
        spec[p + 'linear2.weight'] = ((F, H), std)
        spec[p + 'linear2.bias'] = ((H,), 'zeros')
        for norm in ('norm1', 'norm2'):
            spec[p + norm + '.weight'] = ((H,), 'ones')
            spec[p + norm + '.bias'] = ((H,), 'zeros')
    spec.update({
        'bert.pooler.dense.weight': ((H, H), std),
        'bert.pooler.dense.bias': ((H,), 'zeros'),
        'cls.transform.weight': ((H, H), std),
        'cls.transform.bias': ((H,), 'zeros'),
        'cls.layer_norm.weight': ((H,), 'ones'),
        'cls.layer_norm.bias': ((H,), 'zeros'),
        'cls.decoder_bias': ((V,), 'zeros'),
        'cls.seq_relationship.weight': ((H, 2), std),
        'cls.seq_relationship.bias': ((2,), 'zeros'),
    })
    return spec


def buffer_spec(cfg):
    return {}


# ---------------------------------------------------------------- program

def build(cfg, deterministic=False):
    """The program's (net, loss, optimizer) for this configuration.
    `deterministic` builds the twin whose dropout is off: the hardware PRNG
    of the dropout kernels cannot be followed by any reference."""
    from paddle_tpu import optimizer
    from paddle_tpu.text.bert import BertConfig, BertForPretraining
    drop = 0.0 if deterministic else None
    net = BertForPretraining(BertConfig(
        vocab_size=cfg['vocab_size'], hidden_size=cfg['hidden_size'],
        num_hidden_layers=cfg['num_hidden_layers'],
        num_attention_heads=cfg['num_attention_heads'],
        intermediate_size=cfg['intermediate_size'],
        hidden_act=cfg['hidden_act'],
        hidden_dropout_prob=(cfg['hidden_dropout_prob']
                             if drop is None else drop),
        attention_probs_dropout_prob=(cfg['attention_probs_dropout_prob']
                                      if drop is None else drop),
        max_position_embeddings=cfg['max_position_embeddings'],
        type_vocab_size=cfg['type_vocab_size'],
        initializer_range=cfg['initializer_range']))
    net.train()
    o = cfg['optimizer']
    opt = optimizer.AdamW(learning_rate=o['learning_rate'], beta1=o['beta1'],
                          beta2=o['beta2'], epsilon=o['epsilon'],
                          weight_decay=o['weight_decay'])
    return net, net.pretraining_loss, opt


def stochastic(cfg):
    return bool(cfg['hidden_dropout_prob']
                or cfg['attention_probs_dropout_prob'])


def first_gradient(cfg, slots, start):
    """The gradient AdamW was handed on its first step, from a parameter's
    slots after that step: moment1 = (1 - beta1) * g. (`start`, the
    parameter before the step, is not needed: the decay is decoupled.)"""
    return slots['moment1'] / (1.0 - cfg['optimizer']['beta1'])


# ------------------------------------------------------------------- data

def make_pool(cfg, traffic, seed, batches, rows):
    """`batches` host batches of `rows` rows, ((ids, segments, mask, masked
    positions), (MLM labels, NSP labels)), as `create_pretraining_data.py`
    shapes them: full-length rows but for `short_seq_prob` of them, whose
    length is uniform in [2, seq]; 15% of a row's tokens, at most
    `max_predictions`, are predicted, the rest of the slots carry label -1.
    The tokens are random, the labels can be learned: a position's MLM label
    is the token that stands there, the next-sentence label is the parity
    of the first token."""
    rs = np.random.default_rng([int(seed), 0xBE27])
    seq, k = traffic['seq_len'], traffic['max_predictions']
    n = batches * rows
    lengths = np.where(rs.random(n) < traffic['short_seq_prob'],
                       rs.integers(2, seq + 1, n), seq)
    mask = (np.arange(seq)[None, :] < lengths[:, None]).astype(np.int32)
    ids = rs.integers(1000, cfg['vocab_size'], (n, seq)).astype(np.int32) * mask
    first_half = lengths // 2
    segments = ((np.arange(seq)[None, :] >= first_half[:, None])
                .astype(np.int32) * mask)
    # masked positions: a random subset of each row's real tokens
    order = np.argsort(rs.random((n, seq)) + (1 - mask) * 2.0, axis=1)
    positions = np.sort(order[:, :k], axis=1).astype(np.int32)
    n_pred = np.clip(np.rint(lengths * traffic['masked_lm_prob']), 1, k)
    keep = np.arange(k)[None, :] < n_pred[:, None]
    positions = np.where(keep, positions, 0).astype(np.int32)
    labels = np.where(keep, np.take_along_axis(ids, positions, axis=1),
                      -1).astype(np.int32)
    nsp = (ids[:, :1] % 2).astype(np.int32)
    out = []
    for b in range(batches):
        s = slice(b * rows, (b + 1) * rows)
        out.append(((ids[s], segments[s], mask[s], positions[s]),
                    (labels[s], nsp[s])))
    return out


def augment(traffic, batch, rs):
    """Nothing is done to a text batch on its way to the device."""
    return batch


# ------------------------------------------------------------- operations

def flops_per_sample(cfg, traffic):
    """Operations one sequence's forward and backward passes REQUIRE:
    2 per multiply-add, three passes (forward, and the backward's two
    products), matrix multiplications only. Recomputation, dropout, norms,
    softmax, the optimizer and the embedding look-ups do not count; the MLM
    head counts on the predicted positions only, the pooler and the NSP
    head on one position."""
    L, H, F = traffic['seq_len'], cfg['hidden_size'], cfg['intermediate_size']
    V, k = cfg['vocab_size'], traffic['max_predictions']
    per_token = cfg['num_hidden_layers'] * (4 * H * H + 2 * H * F)
    attention = cfg['num_hidden_layers'] * 2 * L * L * H    # QK^T and PV
    head = k * (H * H + V * H) + H * H + 2 * H
    return 6.0 * (L * per_token + attention + head)
