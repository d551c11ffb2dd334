"""ERNIE dygraph finetune with a new sequence length every step.

Split out of test_convergence.py: it compiles eagerly for ~17 distinct
lengths (about 200 s of real compile work on the CPU), which alone is most
of one xdist worker's share under ``--dist loadfile`` — in a file of its own
the other convergence tests run beside it instead of behind it."""
import numpy as np

import paddle_tpu as paddle


def test_ernie_finetune_dygraph_dynamic_shapes_converges():
    """ERNIE-tiny classification finetune in DYGRAPH mode with a different
    sequence length every step (the BASELINE 'ERNIE-large finetune
    (dygraph Tracer path, dynamic shapes)' config at CI scale): eager
    tensors retrace nothing, grads flow, smoothed loss decreases."""
    from paddle_tpu.text import ErnieConfig, ErnieModel
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F

    paddle.seed(0)
    cfg = ErnieConfig(vocab_size=120, hidden_size=48, num_hidden_layers=2,
                      num_attention_heads=2, intermediate_size=96,
                      max_position_embeddings=48)
    encoder = ErnieModel(cfg)
    head = nn.Linear(48, 2)
    encoder.train()
    params = list(encoder.parameters()) + list(head.parameters())
    opt = paddle.optimizer.AdamW(learning_rate=1e-3, parameters=params)
    rng = np.random.default_rng(2)
    losses = []
    for step in range(24):
        L = int(rng.integers(8, 33))          # dynamic shapes every step
        ids = rng.integers(6, 120, (8, L)).astype('int64')  # never 5
        # balanced by construction: half the rows get token 5 planted at a
        # random position — the head cannot win on class prior alone, the
        # pooled output must actually mix sequence content
        labels = rng.permutation(np.repeat([0, 1], 4)).astype('int64')
        for i, y in enumerate(labels):
            if y:
                ids[i, rng.integers(0, L)] = 5
        _, pooled = encoder(paddle.to_tensor(ids))
        loss = F.cross_entropy(head(pooled), paddle.to_tensor(labels))
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss.numpy()))
    thirds = [np.mean(losses[:8]), np.mean(losses[8:16]),
              np.mean(losses[16:])]
    assert thirds[0] > thirds[2], thirds
    assert all(np.isfinite(losses))
