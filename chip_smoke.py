"""Proof that the system's normal train and serve paths start on the chip.

    python chip_smoke.py             # one TPU chip: every phase below
    python chip_smoke.py --chips 4   # four chips: the sharded step (and the
                                     # kernels it is made of) against its
                                     # one-device comparison, nothing else

One process, full BERT-large width (24 x 1024 x 16 heads, FFN 4096, vocab
30522), random weights from ``--seed``, every phase through the entry points
a user calls:

- ``kernels``   the Pallas flash kernel against the XLA reference (causal,
                key-padding bias) at seq 512, and the in-kernel hardware-PRNG
                dropout (flash attention and fused dropout+add+LayerNorm):
                same seed -> same output, other seed -> other output, finite
                gradients; the dropout-on flash backward against finite
                differences of its own forward along a direction of q, k
                and v. Interpret mode stubs that PRNG, so only a chip
                checks it. The delta rule's backward kernel against finite
                differences of its forward kernel along a direction of q,
                k, v, g and beta; the short convolution's likewise, along
                a direction of its input and of its taps.
- ``train``     ``BertForPretraining`` through ``engine.build_train_step(
                net=, loss=, optimizer=AdamW)``, bf16 compute
                (``amp.auto_cast``), dropout on, donation on: a few dozen
                steps at seq 128 and at seq 512 on one batch whose labels
                can be learned. Loss finite and ending clearly below where
                it began, the first update AdamW's known answer, no compile
                after the first step of a shape, and the seq-512 step holds
                the Pallas kernels (``tpu_custom_call`` under the
                ``flash_attention.pallas`` scope, nothing under
                ``flash_attention.xla``).
- ``hybrid``    the cut Kimi-Linear configuration of the benchmark (5 layers
                at the published widths: delta-rule linear attention, NoPE
                latent attention through the flash kernels with 192-wide
                q/k, 128-wide v and the document mask, a dense SwiGLU layer
                and four expert layers holding 8 of 256 experts; 602M
                parameters) through the same ``build_train_step``: a few
                steps on one batch of packed rows of 8192 tokens. Loss
                finite and falling, no compile after the first step, no
                routing assignment dropped, the flash, the delta-rule and
                the short-convolution kernels in the step (and nothing
                under ``delta_rule.xla`` or ``short_conv.xla``).
- ``serve``     the trained BERT-large encoder behind ``ServingEngine.
                register(layer=, example=, bucket_spec=)``: requests of mixed
                lengths through ``submit``, all ``ok``, no compile after
                warm-up, outputs equal to a direct forward.
- ``generate``  ``register(generative=, page_size=16)`` ->
                ``PagedGenerativeRunner`` with ``TinyCausalLM`` at embed 1024
                / 16 heads / vocab 30522 / max_seq 512: prefill + decode,
                tokens equal to ``reference_decode``. That spec is one
                attention block and NOT a real model (ROADMAP queue 2 item 0
                brings one): the point is that the paged prefill and decode
                programs compile and run on the chip.

It refuses to start unless ``jax.devices()[0].platform == 'tpu'`` and sets
no platform itself. A phase that fails raises: the exit code is non-zero and
the last line says ``"ok": false``. The last line of stdout is one JSON
object, ``{"ok": ..., "device": {"platform", "kind", "count"}}``; per-phase
facts go on the lines before it. Step times are printed as facts of this
run, not as results: the benchmark defines what is measured.
"""
import argparse
import json
import sys
import time
import traceback

import numpy as np

# full width; depth is BERT-large's own 24 layers
FULL = dict(
    layers=24, hidden=1024, heads=16, ffn=4096, vocab=30522, max_pos=512,
    train=((128, 64, 30), (512, 16, 24)),   # (seq, batch, steps)
    lr=2e-5,        # see make_step
    fall=0.5,       # last loss < first - this: > 2x the ~0.2 step-to-step
                    # noise dropout makes at this loss
    serve_len=128, serve_requests=36, serve_buckets=(1, 8),
    lm=dict(vocab=30522, embed=1024, num_heads=16, max_seq=512,
            max_batch=4, prompt_buckets=(16, 64)),
    lm_prompts=(7, 7, 33, 33), lm_new_tokens=3,
    kernel_shape=(2, 16, 512, 64),
    hybrid=dict(                            # benchmark/configs/kimi-linear-*
        config=dict(vocab_size=20480, hidden_size=2304, num_hidden_layers=5,
                    num_attention_heads=32, head_dim=128,
                    intermediate_size=9216, moe_intermediate_size=1024,
                    num_experts=256, num_experts_per_token=8,
                    experts_held=(0, 8), kv_lora_rank=512,
                    qk_nope_head_dim=128, qk_rope_head_dim=64,
                    v_head_dim=128, recompute=True),
        seq=8192, rows=2, steps=8, lr=1e-4, fall=0.3),
    sharded=(128, 32, 30),                  # --chips 4: (seq, batch, steps)
    sharded_kernels=(8, 16, 512, 64),       # --chips 4: flash (B, H, L, D)
)


def say(phase, **facts):
    """One JSON line of facts; every line also carries the process's running
    totals of backend compiles and compile seconds."""
    print(json.dumps(dict(phase=phase, compiles_total=Compiles.count(),
                          compile_seconds_total=Compiles.seconds(), **facts),
                     sort_keys=True), flush=True)


def device_facts():
    import jax
    d = jax.devices()
    return {'platform': d[0].platform, 'kind': d[0].device_kind,
            'count': len(d)}


class Compiles:
    """Backend compiles and seconds spent in them (``jax.compiles`` /
    ``jax.compile_ms`` of the observability spine) plus JAX's own
    persistent-cache hit count."""

    def __init__(self):
        import jax
        self.cache_hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, name, **kw):
        if name == '/jax/compilation_cache/cache_hits':
            self.cache_hits += 1

    @staticmethod
    def count():
        from paddle_tpu import observability as obs
        return int(obs.snapshot()['counters'].get('jax.compiles', 0))

    @staticmethod
    def seconds():
        from paddle_tpu import observability as obs
        return round(float(
            obs.snapshot()['counters'].get('jax.compile_ms', 0)) / 1e3, 2)


def memory_stat(key):
    """``key`` of every device's ``memory_stats()`` (0 where the backend
    reports none, as the CPU does)."""
    import jax
    return [int((d.memory_stats() or {}).get(key, 0)) for d in jax.devices()]


def peak_bytes():
    return memory_stat('peak_bytes_in_use')


# ---------------------------------------------------------------- kernels

def phase_kernels(size, rehearsal):
    from paddle_tpu.kernels import checks
    t0 = time.perf_counter()
    errs = checks.check_flash_against_reference(size['kernel_shape'],
                                                interpret=rehearsal)
    dropout_backward = delta_rule_backward = short_conv_backward = None
    packed = rotary = None
    if not rehearsal:   # interpret mode has no hardware PRNG (and takes
        checks.check_flash_dropout()    # a minute over the delta rule)
        checks.check_norm_dropout()
        dropout_backward = checks.check_flash_dropout_backward()
        packed = checks.check_flash_packed()
        delta_rule_backward = checks.check_delta_rule_backward()
        short_conv_backward = checks.check_short_conv_backward()
        rotary = checks.check_rotary()
    say('kernels', shape=list(size['kernel_shape']), max_abs_err=errs,
        dropout_checked=not rehearsal, dropout_backward=dropout_backward,
        packed_rel_err=packed,
        delta_rule_backward=delta_rule_backward,
        short_conv_backward=short_conv_backward, rotary_rel_err=rotary,
        seconds=round(time.perf_counter() - t0, 2))


# ------------------------------------------------------------------ train

def build_bert(size, seed, dropout=0.1):
    import paddle_tpu as paddle
    from paddle_tpu.text.bert import BertConfig, BertForPretraining
    paddle.seed(seed)
    cfg = BertConfig(
        vocab_size=size['vocab'], hidden_size=size['hidden'],
        num_hidden_layers=size['layers'],
        num_attention_heads=size['heads'], intermediate_size=size['ffn'],
        max_position_embeddings=size['max_pos'],
        hidden_dropout_prob=dropout, attention_probs_dropout_prob=dropout)
    net = BertForPretraining(cfg)
    net.train()                      # hidden + attention dropout on
    return net


def pretrain_batch(size, seq, batch, seed):
    """One padded MLM+NSP batch in the (batch_x, batch_y) form the engine's
    net= steps take: ~15% masked positions, real lengths between seq/2 and
    seq. The tokens are random but the labels can be learned: the MLM label
    of a position is the token that stands there, the next-sentence label
    is the parity of the first token — so a step that trains makes the loss
    of this batch fall far below where it starts (ln 30522 + ln 2 = 11.02),
    not merely wander inside the noise of dropout."""
    rs = np.random.RandomState(seed)
    n_masked = max(seq * 15 // 100, 1)
    lengths = rs.randint(seq // 2, seq + 1, (batch,))
    lengths[0] = seq
    mask = (np.arange(seq)[None, :] < lengths[:, None]).astype(np.int32)
    ids = rs.randint(1, size['vocab'], (batch, seq)).astype(np.int32) * mask
    masked = np.stack([rs.choice(seq // 2, n_masked, replace=False)
                       for _ in range(batch)]).astype(np.int32)
    bx = (ids, np.zeros((batch, seq), np.int32), mask, masked)
    by = (np.take_along_axis(ids, masked, axis=1), ids[:, :1] % 2)
    import jax
    return jax.tree_util.tree_map(jax.numpy.asarray, (bx, by))


def make_step(net, size, sharding=None):
    from paddle_tpu import engine, optimizer
    from paddle_tpu.nn.layer_base import buffer_values, param_values
    # a constant rate, no warm-up (the compiled step holds the rate as a
    # constant). Adam's first bias-corrected step is lr * g / (|g| + eps):
    # EVERY weight moves by the full rate whatever its gradient's size
    # (``check_first_update`` holds the step to exactly that), 335M
    # coherent moves that push post-LN BERT-large far outside the linear
    # range — the loss rises on steps 1-2 and falls from step 3 on (11.25
    # -> 13.61 -> 12.89 -> 11.24 at 2e-5, -> 14.53 at 1e-4, my chip runs,
    # PR 23; with warm moments the same rate falls from its first step).
    # It is why BERT is trained with a warm-up; here the rate is kept small
    # and the run long enough to come out far below where it began.
    opt = optimizer.AdamW(learning_rate=size['lr'], weight_decay=0.01)
    step = engine.build_train_step(net=net, loss=net.pretraining_loss,
                                   optimizer=opt, sharding=sharding)
    state = step.init_state(param_values(net), buffer_values(net))
    return opt, step, state


def run_steps(step, state, batch, n, probe=None, each=None):
    """n steps on one batch. Returns (state, losses, step_ms, compiles
    after the first step[, |first update| / rate of parameter ``probe``]).
    ``each`` is handed every step's result."""
    from paddle_tpu import amp
    from paddle_tpu.core import rng
    losses, ms, flat_from, moved = [], [], None, None
    for i in range(n):
        if probe and i == 0:    # the step donates its state: copy first
            before = np.asarray(state['params'][probe])
        t0 = time.perf_counter()
        with amp.auto_cast(dtype='bfloat16'):
            state, res = step(state, batch, rng.next_key())
        res.loss.raw.block_until_ready()
        ms.append(round((time.perf_counter() - t0) * 1e3, 1))
        losses.append(float(res.loss))
        if each is not None:
            each(res)
        if i == 0:
            flat_from = Compiles.count()
            if probe:
                moved = float(np.median(np.abs(
                    np.asarray(state['params'][probe]) - before))
                    ) / step.optimizer.get_lr()
    out = (state, losses, ms, Compiles.count() - flat_from)
    return out + (moved,) if probe else out


def check_first_update(what, moved):
    """The optimizer's own known answer: after ONE AdamW step from zero
    moments a weight has moved by lr * (g / (|g| + 1e-8) + 0.01 * w), so
    the median |move| of a dense weight is the rate itself."""
    if not 0.9 < moved < 1.01:
        raise AssertionError(
            '%s: after the first step the median weight moved by %.4f of '
            'the learning rate, AdamW moves it by 1' % (what, moved))


def check_losses(what, losses, fall):
    """Finite, and the last loss below the first by a clear margin. The
    batch is fixed and its labels can be learned (``pretrain_batch``), so a
    step that trains ends far below its start, Adam's first-step rise
    (``make_step``) included."""
    if not all(np.isfinite(losses)):
        raise AssertionError('%s: non-finite loss %s' % (what, losses))
    if not losses[-1] < losses[0] - fall:
        raise AssertionError('%s: loss did not fall by %g: %s'
                             % (what, fall, losses))


def step_hlo_facts(step, state, batch):
    """Which attention path the lowered step holds."""
    from paddle_tpu import amp
    from paddle_tpu.core import rng
    with amp.auto_cast(dtype='bfloat16'):
        text = step._jit.lower(state, batch, rng.next_key()).as_text(
            debug_info=True)
    return {'tpu_custom_call': text.count('tpu_custom_call'),
            'scopes': {s: text.count(s) for s in (
                'flash_attention.pallas', 'flash_attention.xla',
                'fused_dropout_norm.pallas', 'fused_dropout_norm.xla',
                'fused_layer_norm.pallas', 'fused_layer_norm.xla',
                'delta_rule.pallas', 'delta_rule.xla',
                'short_conv.pallas', 'short_conv.xla')}}


def _ffn_weight(state):
    """The largest 2-D parameter that is not an embedding table (whose
    absent tokens' rows get no gradient): an FFN weight."""
    dense = {n: v for n, v in state['params'].items()
             if v.ndim == 2 and 'embedding' not in n}
    return max(dense, key=lambda n: dense[n].size)


def phase_train(size, seed, rehearsal):
    import jax
    net = build_bert(size, seed)
    opt, step, state = make_step(net, size)
    if not rehearsal and not step.donates:
        raise AssertionError('the train step does not donate its state')
    for seq, batch_size, steps in size['train']:
        batch = pretrain_batch(size, seq, batch_size, seed + seq)
        hlo = step_hlo_facts(step, state, batch)
        if not rehearsal and seq >= 512:
            sc = hlo['scopes']
            if not (hlo['tpu_custom_call'] and sc['flash_attention.pallas']
                    and not sc['flash_attention.xla']):
                raise AssertionError(
                    'seq %d step does not hold the Pallas flash kernels: %s'
                    % (seq, hlo))
        c0, s0 = Compiles.count(), Compiles.seconds()
        # the first shape starts from zero moments: hold its first update
        # to AdamW's known answer on one FFN weight
        probe = _ffn_weight(state) if seq == size['train'][0][0] else None
        state, losses, ms, after_first, *moved = run_steps(
            step, state, batch, steps, probe=probe)
        if probe:
            check_first_update('train seq %d, %s' % (seq, probe), moved[0])
        check_losses('train seq %d' % seq, losses, size['fall'])
        if after_first:
            raise AssertionError('train seq %d: %d compile(s) after the '
                                 'first step' % (seq, after_first))
        say('train', seq=seq, batch=batch_size, steps=steps, losses=losses,
            step_ms=ms, compiles=Compiles.count() - c0,
            compiles_after_first_step=after_first,
            compile_seconds=round(Compiles.seconds() - s0, 2),
            first_update_over_lr=moved[0] if probe else None,
            donates=step.donates, hlo=hlo, peak_bytes_in_use=peak_bytes())
    # hand the trained weights back to the eager net (what engine.fit does)
    from paddle_tpu import engine
    engine.write_back_state(net, None, state)
    jax.block_until_ready(state['params'])
    return net


# ----------------------------------------------------------------- hybrid

def packed_batch(vocab, seq, rows, seed):
    """Packed rows: documents of random lengths fill each row exactly; the
    next id is, half the time, the current id plus one (learnable); a
    position's label is the next id where that lies in the same document,
    else -1. -> ((ids, segment ids, labels), ())."""
    rs = np.random.default_rng(seed)
    ids = rs.integers(0, vocab, (rows, seq)).astype(np.int32)
    cuts = rs.random((rows, seq)) < 8.0 / seq
    cuts[:, 0] = False
    seg = np.cumsum(cuts, axis=1).astype(np.int32)
    inside = np.concatenate([np.zeros((rows, 1), bool),
                             seg[:, 1:] == seg[:, :-1]], axis=1)
    copy = inside & (rs.random((rows, seq)) < 0.5)
    for t in range(1, seq):
        ids[:, t] = np.where(copy[:, t], (ids[:, t - 1] + 1) % vocab,
                             ids[:, t])
    labels = np.full((rows, seq), -1, np.int32)
    labels[:, :-1] = np.where(inside[:, 1:], ids[:, 1:], -1)
    return (ids, seg, labels), ()


def phase_hybrid(size, seed, rehearsal):
    """The cut Kimi-Linear configuration through the train engine."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu import engine, optimizer
    from paddle_tpu.nn.layer_base import buffer_values, param_values
    from paddle_tpu.text.kimi_linear import (KimiLinearConfig,
                                             KimiLinearForCausalLM)
    h = size['hybrid']
    paddle.seed(seed)
    holder = {}

    def abstract():         # the structure; the weights come from a jit
        holder['net'] = KimiLinearForCausalLM(KimiLinearConfig(**h['config']))
        return param_values(holder['net']), buffer_values(holder['net'])

    shapes, buffer_shapes = jax.eval_shape(abstract)
    net = holder['net']
    net.train()

    def scale(name):
        """A leaf's start, as benchmark/configs/kimi-linear-48b-a3b.json
        states it: norm scales 1, A_log 0, else a normal of this width."""
        if name.endswith(('norm.weight', 'o_norm', 'kv_a_norm')):
            return None
        for tail, std in (('A_log', 0.0), ('dt_bias', 2.0), ('_conv', 0.5),
                          ('o_proj', 0.0027), ('down_proj', 0.0027),
                          ('experts_down', 0.0027),
                          ('embed_tokens.weight', 1.0)):
            if name.endswith(tail):
                return std
        return 0.02

    @jax.jit
    def weights(key):
        keys = jax.random.split(key, len(shapes))
        return {name: (jax.numpy.ones(s.shape, s.dtype)
                       if scale(name) is None else
                       scale(name) * jax.random.normal(k, s.shape, s.dtype))
                for k, (name, s) in zip(keys, sorted(shapes.items()))}

    step = engine.build_train_step(
        net=net, loss=net.training_loss,
        optimizer=optimizer.AdamW(learning_rate=h['lr'], weight_decay=0.1))
    state = step.init_state(
        weights(jax.random.PRNGKey(seed)),
        {k: jax.numpy.zeros(s.shape, s.dtype)
         for k, s in buffer_shapes.items()})
    batch = packed_batch(h['config']['vocab_size'], h['seq'], h['rows'],
                         seed + 27)
    hlo = step_hlo_facts(step, state, batch)
    if not rehearsal and not all(
            hlo['scopes'][k + '.pallas'] and not hlo['scopes'][k + '.xla']
            for k in ('flash_attention', 'delta_rule', 'short_conv')):
        raise AssertionError('the hybrid step does not hold the Pallas '
                             'flash, delta-rule and short-convolution '
                             'kernels: %s' % hlo)
    c0, s0 = Compiles.count(), Compiles.seconds()
    counters = []
    state, losses, ms, after_first = run_steps(
        step, state, batch, h['steps'],
        each=lambda res: counters.append(np.asarray(res.outputs[-1])))
    check_losses('hybrid', losses, h['fall'])
    if after_first:
        raise AssertionError('hybrid: %d compile(s) after the first step'
                             % after_first)
    counted = dict(zip(net.step_counter_names,
                       (float(v) for v in counters[-1])))
    if counted['moe.dropped'] or not counted['moe.assignments_held']:
        raise AssertionError('hybrid: the expert layers dropped or held '
                             'nothing: %s' % counted)
    say('hybrid', seq=h['seq'], rows=h['rows'], steps=h['steps'],
        parameters=int(sum(np.prod(s.shape) for s in shapes.values())),
        losses=losses, step_ms=ms, compiles=Compiles.count() - c0,
        compiles_after_first_step=after_first,
        compile_seconds=round(Compiles.seconds() - s0, 2), counters=counted,
        hlo=hlo, peak_bytes_in_use=peak_bytes())


# ------------------------------------------------------------------ serve

def phase_serve(size, net, seed, rehearsal):
    import jax
    import paddle_tpu as paddle
    from paddle_tpu import serving
    from paddle_tpu.serving.bucketing import BucketSpec
    enc = net.bert
    L, vocab = size['serve_len'], size['vocab']
    example = {'input_ids': np.zeros((L,), np.int32),
               'attention_mask': np.ones((L,), np.int32)}
    eng = serving.ServingEngine()
    ep = eng.register('bert', layer=enc, example=example,
                      bucket_spec=BucketSpec(
                          batch_buckets=size['serve_buckets']))
    s0 = Compiles.seconds()
    t0 = time.perf_counter()
    eng.warmup()
    warm_s = round(time.perf_counter() - t0, 2)
    warm_compile_s = round(Compiles.seconds() - s0, 2)
    c0 = Compiles.count()
    rs = np.random.RandomState(seed)
    reqs = []
    for _ in range(size['serve_requests']):
        n = int(rs.randint(4, L + 1))               # mixed real lengths,
        mask = (np.arange(L) < n).astype(np.int32)  # padded client-side
        reqs.append({'input_ids': (rs.randint(1, vocab, (L,)) * mask
                                   ).astype(np.int32),
                     'attention_mask': mask})
    t0 = time.perf_counter()
    futs = [ep.submit(r) for r in reqs]
    eng.run_until_idle()
    resps = [f.result(timeout=120) for f in futs]
    serve_s = round(time.perf_counter() - t0, 2)
    bad = [r.status for r in resps if not r.ok]
    if bad:
        raise AssertionError('serve: %d request(s) not ok: %s'
                             % (len(bad), bad[:5]))
    compiled = Compiles.count() - c0
    if compiled:
        raise AssertionError('serve: %d compile(s) after warm-up' % compiled)
    # the reference: the layer's own forward on single requests, outside
    # the engine (no batching, no bucket padding; one jitted program with
    # the weights as arguments instead of an op-by-op eager walk, which
    # costs the chip a compile per op)
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.nn.layer_base import (buffer_values, functional_call,
                                          param_values)
    weights = {**param_values(enc, trainable_only=False),
               **buffer_values(enc)}

    @jax.jit
    def direct(weights, ids, mask):
        with paddle.no_grad():
            (seq, pooled), _ = functional_call(
                enc, weights, Tensor(ids), attention_mask=Tensor(mask))
        return seq._value, pooled._value

    worst = 0.0
    for i in (0, len(reqs) // 2, len(reqs) - 1):
        seq, pooled = direct(weights, reqs[i]['input_ids'][None],
                             reqs[i]['attention_mask'][None])
        got_seq, got_pooled = resps[i].outputs
        n = int(reqs[i]['attention_mask'].sum())
        for got, want in ((got_seq[:n], np.asarray(seq)[0, :n]),
                          (got_pooled, np.asarray(pooled)[0])):
            if not np.isfinite(got).all():
                raise AssertionError('serve: non-finite output')
            worst = max(worst, float(np.max(np.abs(
                np.asarray(got, np.float32)
                - np.asarray(want, np.float32)))))
    if not worst < 5e-2:             # bf16 MXU passes, values O(1)
        raise AssertionError('serve: outputs differ from a direct forward '
                             'by %g' % worst)
    say('serve', requests=len(reqs), seq=L, ok=len(resps),
        buckets=list(size['serve_buckets']), warmup_seconds=warm_s,
        warmup_compile_seconds=warm_compile_s,
        serve_seconds=serve_s, compiles_after_warmup=compiled,
        max_abs_diff_vs_direct=worst, peak_bytes_in_use=peak_bytes())


def phase_generate(size, seed):
    from paddle_tpu import serving
    lm = serving.TinyCausalLM.random(seed=seed, **size['lm'])
    eng = serving.ServingEngine()
    ep = eng.register('lm', generative=lm, page_size=16)
    s0 = Compiles.seconds()
    t0 = time.perf_counter()
    eng.warmup()
    warm_s = round(time.perf_counter() - t0, 2)
    warm_compile_s = round(Compiles.seconds() - s0, 2)
    c0 = Compiles.count()
    rs = np.random.RandomState(seed + 1)
    prompts = [rs.randint(1, size['lm']['vocab'], (n,)).astype(np.int32)
               for n in size['lm_prompts']]
    new = size['lm_new_tokens']
    t0 = time.perf_counter()
    futs = [ep.submit({'tokens': p}, max_new_tokens=new) for p in prompts]
    eng.run_until_idle()
    resps = [f.result(timeout=120) for f in futs]
    gen_s = round(time.perf_counter() - t0, 2)
    compiled = Compiles.count() - c0
    bad = [r.status for r in resps if not r.ok]
    if bad:
        raise AssertionError('generate: %d request(s) not ok: %s'
                             % (len(bad), bad))
    if compiled:
        raise AssertionError('generate: %d compile(s) after warm-up'
                             % compiled)
    for p, r in zip(prompts, resps):
        got = [int(t) for t in np.asarray(r.outputs['tokens']).ravel()]
        want = [int(t) for t in lm.reference_decode(p, new)]
        if got != want:
            raise AssertionError(
                'generate: prompt of %d tokens decoded %s, reference_decode '
                'says %s' % (len(p), got, want))
    say('generate', spec='TinyCausalLM (one attention block, NOT a real '
        'model: checks that paged prefill/decode compile and run)',
        prompts=list(size['lm_prompts']), new_tokens=new,
        programs=len(size['lm']['prompt_buckets']) + 1,
        warmup_seconds=warm_s,
        warmup_compile_seconds=warm_compile_s,
        generate_seconds=gen_s, compiles_after_warmup=compiled,
        tokens_equal_reference=True, peak_bytes_in_use=peak_bytes())


# ----------------------------------------------------------- four chips

def phase_partitioned_kernels(size, mesh, rehearsal):
    """The Pallas kernels in a jit whose operands are split over the data
    mesh — what every sharded step is made of — against one device, same
    seed: forward, backward and the hardware-PRNG dropout masks."""
    from paddle_tpu.kernels import checks
    t0 = time.perf_counter()
    b, h, L, d = size['sharded_kernels']
    diffs = checks.check_partitioned(
        mesh, 'data', shape=(b, h, L, d), hidden=h * d,
        # interpret mode has no hardware PRNG
        dropout_p=0.0 if rehearsal else 0.1, interpret=rehearsal)
    say('partitioned_kernels', shape=[b, h, L, d], mesh={'data': mesh.size},
        dropout_checked=not rehearsal, max_rel_diff_vs_one_device=diffs,
        seconds=round(time.perf_counter() - t0, 2))


def phase_sharded(size, seed, chips, rehearsal):
    """FSDP over a ``chips``-device data mesh against the same steps
    replicated on one device: same seed, same batch. Dropout is off on both
    sides so that the losses compare the math alone (that the kernels'
    dropout masks do not depend on the partitioning is
    ``phase_partitioned_kernels``' claim). The partitioned step must hold
    its Pallas kernels: the TPU compiler cannot partition a Mosaic kernel,
    so the kernel sites split themselves over the mesh
    (``kernels._common.spmd_kernel``) — a step that took an XLA path
    instead fails here."""
    import jax
    from jax.sharding import Mesh
    import paddle_tpu as paddle
    from paddle_tpu.distributed.strategy import ShardingConfig
    devices = jax.devices()
    if len(devices) != chips:
        raise AssertionError('--chips %d, but JAX sees %d device(s)'
                             % (chips, len(devices)))
    mesh = Mesh(np.asarray(devices), ('data',))
    phase_partitioned_kernels(size, mesh, rehearsal)
    seq, batch_size, steps = size['sharded']
    batch = pretrain_batch(size, seq, batch_size, seed + seq)

    def run(sharding):
        net = build_bert(size, seed, dropout=0.0)
        _, step, state = make_step(net, size, sharding=sharding)
        info = step.sharding_info(state)
        hlo = step_hlo_facts(step, state, batch)
        paddle.seed(seed + 1)
        c0, s0 = Compiles.count(), Compiles.seconds()
        state, losses, ms, after_first = run_steps(step, state, batch, steps)
        if after_first:
            raise AssertionError('%d compile(s) after the first step'
                                 % after_first)
        in_use = memory_stat('bytes_in_use')
        facts = dict(losses=losses, step_ms=ms, hlo=hlo,
                     compiles=Compiles.count() - c0,
                     compile_seconds=round(Compiles.seconds() - s0, 2),
                     param_bytes_per_device=info['param_bytes_per_device'],
                     bytes_in_use=in_use)
        del state, step, net
        return facts

    one = run(None)
    say('replicated_one_device', seq=seq, batch=batch_size, **one)
    many = run(ShardingConfig(mesh=mesh))
    say('fsdp', seq=seq, batch=batch_size, mesh={'data': chips}, **many)
    xla = sorted(k for k, n in many['hlo']['scopes'].items()
                 if k.endswith('.xla') and n)
    if not rehearsal and (xla or many['hlo']['tpu_custom_call']
                          < one['hlo']['tpu_custom_call']):
        raise AssertionError(
            'the partitioned step lost Pallas kernels: %s (one device: %s)'
            % (many['hlo'], one['hlo']))
    check_losses('fsdp', many['losses'], size['fall'])
    # bf16 compute, batch reduced over `chips` devices instead of one: the
    # math is the same, the summation order is not (docs/PERF.md, "Sharded
    # training": bitwise only against a replicated step on the SAME mesh).
    # Held over the first steps; two bf16 runs drift apart as they train,
    # so the later steps' difference is reported, not held.
    rel = np.abs(np.asarray(many['losses']) / np.asarray(one['losses']) - 1)
    head = min(5, steps)
    np.testing.assert_allclose(many['losses'][:head], one['losses'][:head],
                               rtol=2e-2)
    ratio = many['param_bytes_per_device'] / one['param_bytes_per_device']
    if not ratio < 1.0 / chips + 0.05:
        raise AssertionError('param bytes per device %.3f of replicated, '
                             'expected ~1/%d' % (ratio, chips))
    # (the CPU backend of a rehearsal reports no memory statistics)
    if not rehearsal and not all(b > 0 for b in many['bytes_in_use']):
        raise AssertionError('a device holds nothing: bytes_in_use %s'
                             % many['bytes_in_use'])
    say('sharded_vs_replicated', param_bytes_ratio=round(ratio, 4),
        max_rel_loss_diff_first_steps=float(rel[:head].max()),
        max_rel_loss_diff_all_steps=float(rel.max()),
        peak_bytes_in_use=peak_bytes())


# ------------------------------------------------------------------- main

def run(size, chips=1, seed=0, rehearsal=False):
    """Every phase in order; raises on the first failure. ``rehearsal`` is
    for tests/test_chip_smoke.py only: a tiny ``size`` on the CPU, kernels
    in interpret mode, no platform refusal. ``main`` never sets it."""
    import jax
    dev = device_facts()
    if not rehearsal and dev['platform'] != 'tpu':
        raise SystemExit(
            'chip_smoke: needs a TPU, JAX found %s' % json.dumps(dev))
    from paddle_tpu import inference, observability as obs
    cache_dir = inference.enable_compilation_cache()
    obs.enable()
    compiles = Compiles()
    say('start', device=dev, jax=jax.__version__, seed=seed, chips=chips,
        compile_cache_dir=cache_dir)
    t0 = time.perf_counter()
    if chips == 1:
        phase_kernels(size, rehearsal)
        net = phase_train(size, seed, rehearsal)
        phase_serve(size, net, seed, rehearsal)
        del net
        phase_generate(size, seed)
        phase_hybrid(size, seed, rehearsal)
    else:
        phase_sharded(size, seed, chips, rehearsal)
    say('done', seconds=round(time.perf_counter() - t0, 1),
        compile_cache_hits=compiles.cache_hits,
        compile_cache_dir=cache_dir)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--chips', type=int, choices=(1, 4), default=1)
    ap.add_argument('--seed', type=int, default=0)
    args = ap.parse_args(argv)
    ok = False
    try:
        run(FULL, chips=args.chips, seed=args.seed)
        ok = True
    except SystemExit as e:
        print(e, file=sys.stderr)
    except BaseException:
        traceback.print_exc()
    sys.stderr.flush()
    print(json.dumps({'ok': ok, 'device': device_facts()}), flush=True)
    return 0 if ok else 1


if __name__ == '__main__':
    sys.exit(main())
