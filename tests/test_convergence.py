"""Model-level convergence (SURVEY §4 E2E promises): LeNet/MNIST accuracy,
BERT-tiny pretrain loss strictly decreasing, Wide&Deep AUC improving."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn


def test_lenet_mnist_converges_above_95():
    """LeNet on (synthetic) MNIST through the real Dataset/DataLoader/hapi
    stack reaches >95% train-split accuracy within two epochs."""
    from paddle_tpu.vision.datasets import MNIST
    from paddle_tpu.vision.models import LeNet
    from paddle_tpu.io import DataLoader

    paddle.seed(42)
    train = MNIST(mode='train', backend=None)

    class Wrapped(paddle.io.Dataset):
        """MNIST items are already float32 (1, 28, 28) in [0, 1]."""

        def __len__(self):
            return len(train)

        def __getitem__(self, i):
            img, lab = train[i]
            return np.asarray(img, np.float32).reshape(1, 28, 28), \
                np.int64(lab)

    loader = DataLoader(Wrapped(), batch_size=64, shuffle=True)
    model = LeNet()
    opt = paddle.optimizer.Adam(learning_rate=2e-3,
                                parameters=model.parameters())
    for epoch in range(2):
        model.train()
        for x, y in loader:
            loss = nn.functional.cross_entropy(model(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
    model.eval()
    correct = total = 0
    for x, y in loader:
        pred = model(x).numpy().argmax(-1)
        correct += int((pred == y.numpy()).sum())
        total += len(pred)
    acc = correct / total
    assert acc > 0.95, f"LeNet train accuracy {acc:.3f} <= 0.95"


def test_bert_tiny_pretrain_loss_strictly_decreases():
    """BERT-tiny MLM+NSP pretraining: smoothed loss strictly decreases
    across thirds of the run."""
    from paddle_tpu.text import BertConfig, BertForPretraining

    paddle.seed(0)
    cfg = BertConfig(vocab_size=200, hidden_size=64, num_hidden_layers=2,
                     num_attention_heads=2, intermediate_size=128,
                     max_position_embeddings=32)
    model = BertForPretraining(cfg)
    model.train()
    opt = paddle.optimizer.AdamW(learning_rate=5e-4,
                                 parameters=model.parameters())
    rng = np.random.default_rng(1)
    B, L, K = 16, 24, 4
    losses = []
    for step in range(30):
        ids = rng.integers(4, 200, (B, L)).astype('int64')
        pos = np.stack([rng.choice(L, K, replace=False)
                        for _ in range(B)]).astype('int64')
        labels = np.take_along_axis(ids, pos, axis=1)
        masked = ids.copy()
        np.put_along_axis(masked, pos, 3, axis=1)    # [MASK]=3
        nsp = rng.integers(0, 2, (B, 1)).astype('int64')
        logits, nsp_logits = model(
            paddle.to_tensor(masked),
            masked_positions=paddle.to_tensor(pos))
        loss = model.pretraining_loss(
            logits, nsp_logits, paddle.to_tensor(labels),
            paddle.to_tensor(nsp))
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss.numpy()))
    thirds = [np.mean(losses[:10]), np.mean(losses[10:20]),
              np.mean(losses[20:])]
    assert thirds[0] > thirds[1] > thirds[2], thirds
    assert all(np.isfinite(losses))


def test_wide_deep_auc_improves():
    """Wide&Deep on synthetic CTR data: held-out AUC after training beats
    the untrained model by a wide margin."""
    from paddle_tpu.rec import WideDeep
    from paddle_tpu.metric import auc

    paddle.seed(5)
    rng = np.random.default_rng(2)
    slots = [50, 30, 20]
    n = 2048
    sparse = np.stack([rng.integers(0, v, n) for v in slots],
                      axis=1).astype('int64')
    dense = rng.standard_normal((n, 8)).astype('float32')
    # clickiness depends on slot-0 id parity and dense[0]
    score = (sparse[:, 0] % 2) * 1.5 + dense[:, 0] - 0.75
    y = (score + rng.normal(0, 0.3, n) > 0).astype('int64')
    n_train = 1536
    model = WideDeep(slots, dense_dim=8, embedding_dim=8,
                     hidden_sizes=(64, 32))

    def eval_auc():
        model.eval()
        logits = model(paddle.to_tensor(sparse[n_train:]),
                       paddle.to_tensor(dense[n_train:]))
        p = 1.0 / (1.0 + np.exp(-logits.numpy().reshape(-1)))
        return float(auc(p, y[n_train:]).numpy())

    auc_before = eval_auc()
    opt = paddle.optimizer.Adam(learning_rate=5e-3,
                                parameters=model.parameters())
    model.train()
    for step in range(60):
        idx = rng.integers(0, n_train, 256)
        logits = model(paddle.to_tensor(sparse[idx]),
                       paddle.to_tensor(dense[idx]))
        loss = nn.functional.binary_cross_entropy_with_logits(
            logits.reshape([-1]),
            paddle.to_tensor(y[idx].astype('float32')))
        loss.backward()
        opt.step()
        opt.clear_grad()
    auc_after = eval_auc()
    assert auc_after > max(auc_before + 0.1, 0.8), \
        f"AUC {auc_before:.3f} -> {auc_after:.3f}"


def test_resnet_tiny_images_loss_decreases():
    """ResNet-18 NHWC (the TPU conv layout) on a learnable synthetic
    image task: a large first->middle smoothed-loss drop that the tail
    HOLDS (batch-8 BN noise rules out strict monotonicity) — the BASELINE
    'ResNet-50 ImageNet' config's convergence smoke at CI scale."""
    from paddle_tpu.vision.models import resnet18
    import paddle_tpu.nn.functional as F

    paddle.seed(0)
    net = resnet18(num_classes=4, data_format='NHWC')
    net.train()
    opt = paddle.optimizer.Momentum(learning_rate=0.01, momentum=0.9,
                                    parameters=net.parameters())
    rng = np.random.default_rng(0)
    B = 8
    losses = []
    for step in range(18):
        labels = rng.integers(0, 4, (B,))
        # class k brightens quadrant k: a signal a conv stack learns fast
        imgs = rng.normal(0, 0.3, (B, 32, 32, 3)).astype('float32')
        for i, k in enumerate(labels):
            r, c = divmod(int(k), 2)
            imgs[i, r * 16:(r + 1) * 16, c * 16:(c + 1) * 16] += 1.0
        logits = net(paddle.to_tensor(imgs))
        loss = F.cross_entropy(logits,
                               paddle.to_tensor(labels.astype('int64')))
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss.numpy()))
    thirds = [np.mean(losses[:6]), np.mean(losses[6:12]),
              np.mean(losses[12:])]
    # batch-8 BN makes the tail noisy: require a big first->middle drop and
    # the tail to HOLD the gain, not strict monotonicity
    assert thirds[1] < 0.5 * thirds[0], thirds
    assert thirds[2] < 0.5 * thirds[0], thirds
    assert all(np.isfinite(losses))
