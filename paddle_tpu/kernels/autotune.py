"""On-hardware autotuning for the flash-attention dispatch.

The right (block_q, block_k) tiling — and whether the Pallas kernel beats
XLA's fused attention at all — depends on sequence length, head dim,
batch and the mask/dropout mix; fixed constants leave performance on the
table (the round-2 kernel shipped block 512x512 everywhere). This module
times candidates ON THE REAL CHIP once per shape signature:

- ``autotune_attention(...)`` builds a training-shaped step (forward +
  backward, the bench workload) per candidate, times best-of-k, and
  records the winner;
- results cache in-process, keyed by backend + signature: a process that
  has not tuned dispatches by the static heuristic, so no file outside
  what git carries decides which kernel runs;
- the traced attention dispatch (nn/functional/transformer.py) consults
  ``lookup()`` at trace time — shapes are concrete under tracing, timing
  never runs inside a trace;
- timing is budget-capped; a candidate the compiler refuses raises — every
  tiling ``_candidate_blocks`` emits is held to the chip's compiler by
  tests/test_chip_compile.py, so a refusal is a bug, not a slow candidate.
"""
import functools
import math
import time

import jax
import jax.numpy as jnp

__all__ = ['autotune_attention', 'lookup', 'attention_signature',
           'make_device_qkv',
           'clear_cache']

_CACHE = {}


def attention_signature(batch, heads, seq, head_dim, causal, has_kpad,
                        dropout, dtype='bfloat16'):
    return 'attn:%s:%s:b%d_h%d_l%d_d%d_c%d_m%d_p%d' % (
        jax.default_backend(), jnp.dtype(dtype).name, batch, heads, seq,
        head_dim, int(causal), int(has_kpad), int(dropout > 0))


def _valid_decision(d, seq=None):
    if not (isinstance(d, dict) and d.get('mode') in ('flash', 'xla')
            and isinstance(d.get('block_q'), int)
            and isinstance(d.get('block_k'), int)):
        return False
    if d['mode'] == 'flash':
        bq, bk = d['block_q'], d['block_k']
        if bq <= 0 or bk <= 0:
            return False
        if seq is not None and (seq % bq or seq % bk or bq > seq
                                or bk > seq):
            return False
    return True


def lookup(batch, heads, seq, head_dim, causal, has_kpad, dropout,
           dtype='bfloat16'):
    """Cached decision for this signature, or None.

    Returns {'mode': 'flash'|'xla', 'block_q': int, 'block_k': int}.
    Malformed entries are treated as untuned.
    """
    d = _CACHE.get(attention_signature(
        batch, heads, seq, head_dim, causal, has_kpad, dropout, dtype))
    return d if _valid_decision(d, seq) else None


def clear_cache():
    _CACHE.clear()


def _time_step(fn, args, iters=5, warmup=2):
    from ..observability import Stopwatch
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    best = float('inf')
    sw = Stopwatch()
    for _ in range(iters):
        sw.restart()
        out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, sw.elapsed())
    return best


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _qkv_program(key, batch, heads, seq, head_dim, dtype):
    return tuple(jax.random.normal(kk, (batch, heads, seq, head_dim), dtype)
                 for kk in jax.random.split(key, 3))


def make_device_qkv(batch, heads, seq, head_dim, dtype, seed=0):
    """Three [b,h,s,d] standard-normal tensors generated ON DEVICE as one
    jitted program (compiled once per shape signature per process, zero
    host->device transfer)."""
    return _qkv_program(jax.random.PRNGKey(seed), batch, heads, seq,
                        head_dim, jnp.dtype(dtype))


def _candidate_blocks(seq, has_kpad):
    """Tile candidates; with a key-padding bias block_k is pinned to the
    full row (the kernel streams the whole bias), so only block_q varies."""
    bs = [b for b in (128, 256, 512, 1024) if seq % b == 0 and b <= seq]
    if has_kpad:
        return [(bq, seq) for bq in bs]
    return [(bq, bk) for bq in bs for bk in bs]


def autotune_attention(batch, heads, seq, head_dim, dtype='bfloat16',
                       causal=False, has_kpad=False, dropout_p=0.0,
                       budget_s=90.0, verbose=False):
    """Time flash block candidates + the XLA path for one shape signature
    (training step: forward + grads wrt q/k/v); record and return the
    winner. No-op (returns the cached decision) when already tuned.
    """
    sig = attention_signature(batch, heads, seq, head_dim, causal,
                              has_kpad, dropout_p, dtype)
    if _valid_decision(_CACHE.get(sig), seq):
        return _CACHE[sig]

    from .flash_attention import flash_attention_bhld

    dt = jnp.dtype(dtype)
    q, k, v = make_device_qkv(batch, heads, seq, head_dim, dt)
    kpad = None
    if has_kpad:
        kpad = jnp.zeros((batch, seq), dt)
    seed = jnp.zeros((1, 1), jnp.int32) if dropout_p > 0 else None
    scale = 1.0 / math.sqrt(head_dim)

    def make_flash_step(bq, bk):
        def loss(qq, kk, vv):
            out = flash_attention_bhld(
                qq, kk, vv, causal=causal, scale=scale, kpad_bias=kpad,
                dropout_p=dropout_p, dropout_seed=seed,
                block_q=bq, block_k=bk)
            return jnp.sum(out.astype(jnp.float32))
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    def make_xla_step():
        drop_key = jax.random.PRNGKey(0)

        def loss(qq, kk, vv):
            s = jnp.einsum('bhqd,bhkd->bhqk', qq, kk).astype(jnp.float32) \
                * scale
            if causal:
                L = qq.shape[2]
                mask = jnp.tril(jnp.ones((L, L), jnp.bool_))
                s = jnp.where(mask, s, -1e30)
            if kpad is not None:
                s = s + kpad[:, None, None, :].astype(jnp.float32)
            p = jax.nn.softmax(s, axis=-1).astype(qq.dtype)
            if dropout_p > 0:
                # the real XLA fallback applies attention-prob dropout too;
                # the candidates must pay the same costs to compare fairly
                keep = jax.random.bernoulli(drop_key, 1.0 - dropout_p,
                                            p.shape)
                p = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
            out = jnp.einsum('bhqk,bhkd->bhqd', p, vv)
            return jnp.sum(out.astype(jnp.float32))
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    deadline = time.monotonic() + budget_s
    results = []   # (seconds, decision-dict)

    def try_candidate(label, decision, builder, force=False):
        if not force and time.monotonic() > deadline and results:
            return
        t = _time_step(builder(), (q, k, v))
        results.append((t, decision))
        from .. import observability as _obs
        if _obs.enabled():
            # candidate timings belong on the telemetry spine, not
            # only the verbose console (GL014)
            _obs.event('autotune.candidate', sig=sig, label=label,
                       ms=round(t * 1e3, 3))
        if verbose:
            # graftlint: disable=GL014 — opt-in tuning console output;
            # the measurement also lands on the event log above
            print('  autotune %s %s: %.3f ms' % (sig, label, t * 1e3))

    try_candidate('xla', {'mode': 'xla', 'block_q': 0, 'block_k': 0},
                  make_xla_step)
    flash_timed = 0
    if jax.default_backend() == 'tpu':
        cands = _candidate_blocks(seq, has_kpad)
        # the default tiling is always measured even with the budget gone:
        # a decision comparing xla against NO flash candidate could cache a
        # choice worse than the static heuristic
        default = (512, 512) if (512, 512) in cands else \
            (cands[len(cands) // 2] if cands else None)
        for bq, bk in sorted(cands, key=lambda c: c != default):
            before = len(results)
            try_candidate(
                'flash %dx%d' % (bq, bk),
                {'mode': 'flash', 'block_q': bq, 'block_k': bk},
                functools.partial(make_flash_step, bq, bk),
                force=((bq, bk) == default))
            flash_timed += len(results) - before
        if cands and not flash_timed:
            return None   # nothing comparable was measured; don't cache

    if not results:
        return None
    best_t, best = min(results, key=lambda r: r[0])
    best = dict(best, ms=round(best_t * 1e3, 3))
    # record the untuned XLA time too, so benches can report the
    # tuned-vs-untuned delta without re-measuring
    xla_times = [t for t, d in results if d.get('mode') == 'xla']
    if xla_times:
        best['xla_ms'] = round(min(xla_times) * 1e3, 3)
    _CACHE[sig] = best
    return best
