"""Headline benchmark: BERT-large pretrain train-step throughput on one chip.

Runs in ONE process on a TPU and prints a JSON line {"metric", "value",
"unit", "vs_baseline", "device": {platform, device_kind, count}, ...extras}
after each completed section (the last line is the result). Without a TPU it
prints an error and exits non-zero: no number is ever produced off the chip.
``chip_smoke.py`` is the proof that the system's normal train/serve entry
points run on the chip; the hand-written steps timed here are the benchmark
PR's to replace. (The serving/fleet/engine/elastic sections that only the
removed CPU smoke branch ran are gone with it; git history keeps them.)

The full train step (forward + backward + AdamW update) is compiled to a
single XLA computation and runs in TRAIN mode (hidden + attention dropout
active, as the reference pretrains); compute is bfloat16 (TPU MXU-native)
with fp32 master weights, matching the reference's AMP fp16 + loss-scaling
setup (BASELINE.json: BERT pretraining, Fleet c_allreduce path) without
needing a scaler. At seq 512 (pretraining phase 2) attention dominates and
dispatches the Pallas flash kernels (kernels/flash_attention.py), including
in-kernel attention-probability dropout.

Headline metric: phase-1 seq128 samples/sec vs the A100-class baseline in
BASELINE.json; the phase-2 seq512 number is reported in "extras".
"""
import json
import os
import sys
import time

import numpy as np


def _published_baseline(name, fallback):
    """Single source of truth: BASELINE.json 'published' (with provenance)."""
    try:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            'BASELINE.json')
        with open(path) as f:
            return float(json.load(f)['published'][name]['value'])
    except Exception:
        return fallback


BASELINE_SAMPLES_PER_SEC = _published_baseline(
    'bert_large_seq128_samples_per_sec_per_chip', 250.0)
BASELINE_SEQ512_SPS = _published_baseline(
    'bert_large_seq512_samples_per_sec_per_chip', 80.0)


def bench_bert(cfg_kwargs, batch, seq, steps, warmup, train_mode=True,
               use_flat=False):
    # use_flat=False measured best on v5e: XLA overlaps per-tensor optimizer
    # fusions with the tail of the backward pass, while the flat-buffer
    # update serializes behind the full gradient (tools/bench_2x2.py:
    # seq128 489.8 vs 462.1, seq512 89.3 vs 87.1 samples/s)
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.nn.layer_base import functional_call, param_values
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.text.bert import BertConfig, BertForPretraining
    from paddle_tpu import optimizer as opt_mod

    paddle.seed(0)
    cfg = BertConfig(**cfg_kwargs)
    net = BertForPretraining(cfg)
    if train_mode:
        net.train()   # dropout on: benchmark the real pretraining step
    else:
        net.eval()
    params = param_values(net, trainable_only=False)
    opt = opt_mod.AdamW(learning_rate=1e-4, weight_decay=0.01)
    if use_flat:
        # flat-buffer fused update: ONE streaming HBM pass over all 340M
        # params instead of ~400 small per-tensor fusions (optimizer/fused.py)
        flat = opt_mod.FlatFusedUpdate(opt, params)
        flat_p = flat.flatten(params)
        opt_state = flat.init_state(flat_p)
        # the master buffer now owns the weights: drop the model's own eager
        # copies (1.36 GB) — functional_call swaps real values in per step
        for _, p in net.named_parameters():
            p._value = jnp.zeros((1,), jnp.float32)
        for _, b in net.named_buffers():
            b._value = jnp.zeros((1,), jnp.float32)
        del params
    else:
        flat = None
        flat_p = params
        opt_state = opt.init_state_values(params)

    # MLM labels only at masked positions (~15% of seq), the reference's
    # pretraining setup: the vocab-size logits matmul runs on [B, K] gathered
    # positions, not the full [B, S] sequence
    n_masked = max(seq * 15 // 100, 1)
    rs = np.random.RandomState(0)
    input_ids = jnp.asarray(rs.randint(0, cfg.vocab_size, (batch, seq)),
                            jnp.int32)
    token_type_ids = jnp.zeros((batch, seq), jnp.int32)
    masked_positions = jnp.asarray(
        np.stack([rs.choice(seq, n_masked, replace=False)
                  for _ in range(batch)]), jnp.int32)
    mlm_labels = jnp.asarray(
        rs.randint(0, cfg.vocab_size, (batch, n_masked)), jnp.int32)
    nsp_labels = jnp.asarray(rs.randint(0, 2, (batch, 1)), jnp.int32)

    def train_step(flat_p, opt_state, input_ids, token_type_ids,
                   masked_positions, mlm_labels, nsp_labels):
        # f32 master -> named tree (flat mode: slices of the master buffer,
        # zero-copy views since the row packing matches the tiled layout)
        p_tree = flat.unflatten(flat_p) if flat is not None else flat_p

        def loss_of(p):
            # bf16 compute, fp32 master weights (TPU-native mixed precision)
            pc = {k: (v.astype(jnp.bfloat16)
                      if v.dtype == jnp.float32 else v)
                  for k, v in p.items()}
            (logits, nsp), _ = functional_call(
                net, pc, Tensor(input_ids), Tensor(token_type_ids),
                masked_positions=Tensor(masked_positions))
            loss = net.pretraining_loss(
                Tensor(logits._value.astype(jnp.float32)),
                Tensor(nsp._value.astype(jnp.float32)),
                Tensor(mlm_labels), Tensor(nsp_labels))
            return loss._value
        loss, grads = jax.value_and_grad(loss_of)(p_tree)
        if flat is not None:
            new_p, new_opt = flat.update(flat_p, grads, opt_state)
        else:
            new_p, new_opt = opt.functional_update(flat_p, grads, opt_state)
        return new_p, new_opt, loss

    jitted = jax.jit(train_step, donate_argnums=(0, 1))

    loss = None
    for _ in range(warmup):
        flat_p, opt_state, loss = jitted(flat_p, opt_state, input_ids,
                                         token_type_ids, masked_positions,
                                         mlm_labels, nsp_labels)
    if loss is not None:
        float(loss)  # host fetch: forces the full dispatch chain to finish

    t0 = time.perf_counter()
    for _ in range(steps):
        flat_p, opt_state, loss = jitted(flat_p, opt_state, input_ids,
                                         token_type_ids, masked_positions,
                                         mlm_labels, nsp_labels)
    float(loss)
    dt = time.perf_counter() - t0
    return batch * steps / dt


def bench_resnet50(batch, steps, warmup, train_mode=True):
    """ResNet-50 ImageNet train-step throughput (bf16 compute, fp32 master,
    SGD+momentum) vs the A100 baseline in BASELINE.json."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.nn.layer_base import functional_call, param_values, \
        buffer_values
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.vision.models import resnet50
    from paddle_tpu import optimizer as opt_mod
    from paddle_tpu.nn import functional as F

    paddle.seed(0)
    # NHWC end-to-end: the TPU-native conv layout — no transposes anywhere
    # in the hot loop (the reference's cuDNN path needs NCHW; BASELINE's
    # A100 number itself runs NHWC under AMP). PADDLE_TPU_RESNET_S2D=1
    # additionally packs the stem conv 2x2-space-to-depth (exact rewrite,
    # tests/test_resnet_s2d.py) for MXU input-lane utilization.
    s2d = os.environ.get('PADDLE_TPU_RESNET_S2D', '') == '1'
    net = resnet50(num_classes=1000, data_format='NHWC',
                   space_to_depth_stem=s2d)
    if train_mode:
        net.train()
    else:
        net.eval()
    params = param_values(net, trainable_only=False)
    buffers = buffer_values(net)   # BN running stats: threaded through the
    # step explicitly so functional_call restores the originals (no tracer
    # ever leaks into the layer buffers) and stats actually advance
    opt = opt_mod.Momentum(learning_rate=0.1, momentum=0.9,
                           weight_decay=1e-4)
    # ResNet's step is short and op-count-bound (161 small tensors): the
    # flat-buffer update collapses ~1000 per-param update ops into one
    # streaming fusion — the case FlatFusedUpdate is for (optimizer/fused.py)
    flat = opt_mod.FlatFusedUpdate(opt, params)
    flat_p = flat.flatten(params)
    opt_state = flat.init_state(flat_p)

    # Bench inputs are generated ON DEVICE (a [256,224,224,3] bf16 host
    # array would be a 77 MB host->device transfer). Real training feeds
    # via infeed/prefetch; the train-step bench measures compute, so
    # synthetic on-device inputs are the honest setup.
    kimg, klab = jax.random.split(jax.random.PRNGKey(0))
    images = jax.jit(
        lambda k: jax.random.normal(k, (batch, 224, 224, 3), jnp.bfloat16)
    )(kimg)
    labels = jax.jit(
        lambda k: jax.random.randint(k, (batch,), 0, 1000, dtype=jnp.int32)
    )(klab)

    def train_step(flat_p, opt_state, buffers, images, labels):
        p_tree = flat.unflatten(flat_p)

        def loss_of(p):
            pc = {k: (v.astype(jnp.bfloat16)
                      if v.dtype == jnp.float32 else v)
                  for k, v in p.items()}
            pc.update(buffers)
            logits, new_buffers = functional_call(net, pc, Tensor(images))
            loss = F.cross_entropy(
                Tensor(logits._value.astype(jnp.float32)), Tensor(labels))
            return loss._value, new_buffers
        (loss, new_buffers), grads = jax.value_and_grad(
            loss_of, has_aux=True)(p_tree)
        new_p, new_opt = flat.update(flat_p, grads, opt_state)
        return new_p, new_opt, new_buffers, loss

    jitted = jax.jit(train_step, donate_argnums=(0, 1, 2))
    loss = None
    for _ in range(warmup):
        flat_p, opt_state, buffers, loss = jitted(flat_p, opt_state, buffers,
                                                  images, labels)
    if loss is not None:
        float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        flat_p, opt_state, buffers, loss = jitted(flat_p, opt_state, buffers,
                                                  images, labels)
    float(loss)
    dt = time.perf_counter() - t0
    return batch * steps / dt


BASELINE_RESNET50_IPS = _published_baseline(
    'resnet50_images_per_sec_per_chip', 2500.0)


def _env_batch(var, default):
    """Bench batch with env override (for applying batch-sweep results);
    every emitter echoes the batch into its JSON so an override can never
    masquerade as the default run."""
    try:
        batch = int(os.environ.get(var, '0'))
    except ValueError:
        batch = 0
    return batch if batch > 0 else default


def _bert_batch(seq, default):
    return _env_batch('PADDLE_TPU_BERT%d_BATCH' % seq, default)


def _resnet50_batch():
    return _env_batch('PADDLE_TPU_RESNET_BATCH', 256)


def _resnet50_accel_ips():
    """The one ResNet-50 measurement (shared by `bench resnet50` and the
    combined default run so they always agree)."""
    return bench_resnet50(batch=_resnet50_batch(), steps=10, warmup=2)


def _telemetry_counters():
    """Interposed telemetry counters (retraces, compile time, host-transfer
    bytes, and the fault-tolerance tallies: DataLoader worker restarts,
    quarantined samples, watchdog/collective timeouts) for the extras — a
    run that self-healed is flagged as such."""
    from paddle_tpu import observability as obs
    return obs.counters_summary()


def _cost_ledger():
    """Cost-explorer extras: the ledger summary + per-program rows (FLOPs,
    bytes accessed, peak memory, roofline bound against the device's
    published peaks) every compiled program in the bench run registered."""
    from paddle_tpu import observability as obs
    out = obs.costs.summary()
    out['programs_detail'] = [
        {k: e[k] for k in ('program', 'kind', 'flops', 'bytes_accessed',
                           'peak_bytes', 'hits')}
        | {'bound': e['roofline']['bound'],
           'est_ms': e['roofline']['est_ms']}
        for e in obs.costs.ledger()[:40]]
    return out


def main(argv=None):
    """One process, one chip. Exits non-zero — and prints no metric — when
    JAX finds no TPU, on an unknown model, and when any section raises
    (autotune and the dropout check included: on the chip a failure there
    is a failure of the run)."""
    argv = sys.argv[1:] if argv is None else argv
    model = argv[0].lstrip('-').replace('model=', '') if argv else 'bert'
    if model not in ('bert', 'resnet50'):
        print(f"bench.py: unknown model {model!r}: choose bert or resnet50",
              file=sys.stderr)
        return 2
    import jax
    devices = jax.devices()
    device = {'platform': devices[0].platform,
              'device_kind': devices[0].device_kind, 'count': len(devices)}
    if device['platform'] != 'tpu':
        print("bench.py: needs a TPU, JAX found %s — no number is measured "
              "off the chip" % json.dumps(device), file=sys.stderr)
        return 1

    from paddle_tpu import inference, observability as obs
    cache_dir = inference.enable_compilation_cache()
    obs.enable()

    if model == 'resnet50':
        ips = _resnet50_accel_ips()
        print(json.dumps({
            "metric": "resnet50_images_per_sec_per_chip",
            "value": round(ips, 2),
            "unit": "images/sec",
            "vs_baseline": round(ips / BASELINE_RESNET50_IPS, 4),
            "mode": "train (bf16 compute, SGD+momentum)",
            "device": device,
            "batch": _resnet50_batch(),
            "s2d_stem": os.environ.get('PADDLE_TPU_RESNET_S2D', '') == '1',
            "extras": {"telemetry": _telemetry_counters(),
                       "compile_cache_dir": cache_dir},
            "complete": True,
        }))
        return 0

    large = dict(vocab_size=30522, hidden_size=1024,
                 num_hidden_layers=24, num_attention_heads=16,
                 intermediate_size=4096, max_position_embeddings=512)
    # autotune the attention tiling for the two bench signatures on the
    # chip; the decisions (incl. tuned-vs-untuned xla_ms) go into extras
    from paddle_tpu.kernels.autotune import autotune_attention
    budget = float(os.environ.get('PADDLE_TPU_AUTOTUNE_BUDGET', '120'))
    autotune_report = {}
    for b, s in ((_bert_batch(128, 64), 128), (_bert_batch(512, 16), 512)):
        dec = autotune_attention(
            b, 16, s, 64, dtype='bfloat16', causal=False,
            has_kpad=False, dropout_p=0.1, budget_s=budget,
            verbose=False)
        print("autotune b%d l%d -> %s" % (b, s, dec), file=sys.stderr)
        if dec:
            autotune_report["b%d_l%d" % (b, s)] = dec
    # in-kernel HW-PRNG attention dropout (interpret mode stubs the PRNG,
    # so only the chip exercises it): raises on any failed check
    from paddle_tpu.kernels.checks import check_flash_dropout
    check_flash_dropout()
    # A CUMULATIVE result line is printed (and flushed) after EVERY
    # completed section, so a run cut at a time limit keeps what finished.
    # The LAST line printed is the result.
    result = {
        "metric": "bert_large_pretrain_samples_per_sec_per_chip",
        "value": 0.0,
        "unit": "samples/sec",
        "vs_baseline": 0.0,
        "mode": "train (hidden+attention dropout on)",
        "device": device,
        "extras": {
            "autotune": autotune_report,
            "flash_dropout_check": "pass (deterministic, seed-sensitive, "
                                   "finite grads)",
            "compile_cache_dir": cache_dir,
        },
    }
    # phase 1: seq128 (headline, comparable to BASELINE.json)
    b128 = _bert_batch(128, 64)
    sps128 = bench_bert(large, batch=b128, seq=128, steps=10, warmup=2)
    result["value"] = round(sps128, 2)
    result["vs_baseline"] = round(sps128 / BASELINE_SAMPLES_PER_SEC, 4)
    result["batch"] = b128   # echoed so an override can't masquerade
    result["extras"]["telemetry"] = _telemetry_counters()
    print(json.dumps(result), flush=True)
    # phase 2: seq512 — attention-dominated, Pallas flash path
    b512 = _bert_batch(512, 16)
    sps512 = bench_bert(large, batch=b512, seq=512, steps=10, warmup=2)
    result["extras"]["seq512_batch"] = b512
    result["extras"].update({
        "seq512_samples_per_sec": round(sps512, 2),
        "seq512_vs_baseline": round(sps512 / BASELINE_SEQ512_SPS, 4),
        "seq512_baseline": BASELINE_SEQ512_SPS,
    })
    result["extras"]["telemetry"] = _telemetry_counters()
    print(json.dumps(result), flush=True)
    resnet_ips = _resnet50_accel_ips()
    result["extras"].update({
        "resnet50_images_per_sec": round(resnet_ips, 2),
        "resnet50_vs_baseline": round(
            resnet_ips / BASELINE_RESNET50_IPS, 4),
        "resnet50_baseline": BASELINE_RESNET50_IPS,
        "resnet50_batch": _resnet50_batch(),
        "resnet50_s2d_stem": os.environ.get(
            'PADDLE_TPU_RESNET_S2D', '') == '1',
    })
    result["complete"] = True
    result["extras"]["telemetry"] = _telemetry_counters()
    result["extras"]["costs"] = _cost_ledger()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
