"""The generative contract: what a model gives the serving engine to decode.

The decode hot path of a text model is one token per step per sequence;
recomputing attention over the whole prefix each step is O(S^2) per token.
A KV cache stores every layer's keys/values so one decode step is O(S) —
and, crucially for the serving engine, the cache shapes are **static**:
requests join and leave by taking and freeing pages of one pool
(``paged_kv.py``) while the jitted decode step always runs at
``[max_batch]``. No shape ever changes, so nothing ever recompiles (the
Orca/vLLM iteration-level scheduling idea). ``GenerativeSpec`` is the
contract ``paged_runner.PagedGenerativeRunner`` schedules.

Everything here is pure ``jnp`` — safe inside ``jax.jit``; the cache is a
plain dict pytree threaded through the jitted prefill/decode calls.

``TinyCausalLM`` is the reference ``GenerativeSpec`` implementation (one
pre-LN attention block + tied output head): small enough to read in one
sitting, real enough that tests verify cached decode against a full
no-cache forward, token for token.
"""
import numpy as np
import jax
import jax.numpy as jnp

__all__ = ['attend_prompt', 'GenerativeSpec', 'TinyCausalLM']


def attend_prompt(q, k, v):
    """Causal self-attention within one prompt (prefill): ``[Lp, H, D]``
    each. Padded rows beyond the real length produce garbage outputs the
    caller never reads (only the last *real* row's logits matter)."""
    d = q.shape[-1]
    lp = q.shape[0]
    scores = jnp.einsum('ihd,jhd->hij', q, k) / jnp.sqrt(float(d))
    causal = jnp.tril(jnp.ones((lp, lp), bool))[None]
    scores = jnp.where(causal, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum('hij,jhd->ihd', w, v)


class GenerativeSpec:
    """What a model must provide to decode under continuous batching.

    Subclasses implement pure functions over a paged cache + block tables
    (``paged_kv.py`` has the primitives, ``paged_runner.py`` the
    scheduler). All are jitted by the runner, so bodies must be trace-safe
    — no Python branching on traced values:

    - ``init_paged_cache(num_pages, page_size) -> pytree`` of
      ``[.., P, page_size, ..]`` arrays
    - ``prefill_chunk(cache, block_row[MP], tokens[Cb], start, length)
      -> (cache, logits[Cb, V])`` — one chunk of one sequence's prompt
      at absolute offset ``start`` (chunked prefill / prefix-cache
      resume); rows at or beyond ``length`` are bucket padding.
    - ``decode_paged(cache, block_tables[B, MP], tokens[B],
      positions[B]) -> (cache, logits[B, V])`` — one token per row.
    - ``verify_tokens(cache, block_tables[B, MP], tokens[B, K],
      positions[B, K]) -> (cache, logits[B, K, V])`` — process ``K``
      tokens per row in ONE step (the speculative-decoding verify;
      ``decode_paged`` is its ``K=1`` special case).
    """

    max_batch = 1
    max_seq = 128
    eos_id = None                      # None: stop only on max_new_tokens
    prompt_buckets = (16, 32, 64)

    def init_paged_cache(self, num_pages, page_size):
        raise NotImplementedError

    def prefill_chunk(self, cache, block_row, tokens, start, length):
        raise NotImplementedError

    def decode_paged(self, cache, block_tables, tokens, positions):
        cache, logits = self.verify_tokens(
            cache, block_tables, tokens[:, None], positions[:, None])
        return cache, logits[:, 0]

    def verify_tokens(self, cache, block_tables, tokens, positions):
        raise NotImplementedError


class TinyCausalLM(GenerativeSpec):
    """Reference spec: embed + learned positions, one pre-LN causal
    attention block with residual, tied vocab head.

    ``params`` maps ``emb [V,E]``, ``pos [max_seq,E]``, ``wq/wk/wv/wo
    [E,E]``; the output head reuses ``emb`` transposed. Deterministic
    (greedy decode happens in the runner); everything trace-safe.
    """

    def __init__(self, params, num_heads, max_batch=4, max_seq=128,
                 eos_id=None, prompt_buckets=(8, 16, 32)):
        self.p = {k: jnp.asarray(v) for k, v in params.items()}
        vocab, embed = self.p['emb'].shape
        if embed % num_heads:
            raise ValueError("embed dim must divide num_heads")
        self.num_heads = int(num_heads)
        self.head_dim = embed // num_heads
        self.vocab = vocab
        self.max_batch = int(max_batch)
        self.max_seq = int(max_seq)
        self.eos_id = eos_id
        self.prompt_buckets = tuple(sorted(prompt_buckets))

    @classmethod
    def random(cls, vocab=64, embed=32, num_heads=4, max_seq=64, seed=0,
               **kw):
        """Small random instance for tests/benches (numpy RNG, host-side)."""
        r = np.random.RandomState(seed)

        def w(*s):
            return (r.randn(*s) * 0.1).astype(np.float32)
        params = {'emb': w(vocab, embed), 'pos': w(max_seq, embed),
                  'wq': w(embed, embed), 'wk': w(embed, embed),
                  'wv': w(embed, embed), 'wo': w(embed, embed)}
        return cls(params, num_heads, max_seq=max_seq, **kw)

    # -- shared block ---------------------------------------------------
    def _norm(self, x):
        m = jnp.mean(x, axis=-1, keepdims=True)
        v = jnp.var(x, axis=-1, keepdims=True)
        return (x - m) / jnp.sqrt(v + 1e-5)

    def _qkv(self, x):
        h, d = self.num_heads, self.head_dim
        n = self._norm(x)

        def split(w):
            y = n @ w
            return y.reshape(y.shape[:-1] + (h, d))
        return split(self.p['wq']), split(self.p['wk']), split(self.p['wv'])

    def _head(self, y):
        return y @ self.p['emb'].T

    # -- the contract (primitives in paged_kv.py) ------------------------
    def init_paged_cache(self, num_pages, page_size):
        from . import paged_kv
        return paged_kv.create_paged_cache(
            1, num_pages, page_size, self.num_heads, self.head_dim)

    def prefill_chunk(self, cache, block_row, tokens, start, length):
        from . import paged_kv
        cb = tokens.shape[0]
        pos = jnp.minimum(start + jnp.arange(cb), self.max_seq - 1)
        x = self.p['emb'][tokens] + self.p['pos'][pos]        # [Cb, E]
        q, k, v = self._qkv(x)                                # [Cb, H, D]
        cache = paged_kv.write_chunk(cache, 0, block_row, k, v, start,
                                     length)
        out = paged_kv.attend_chunk(cache, 0, q, block_row, start)
        y = x + out.reshape(cb, -1) @ self.p['wo']
        return cache, self._head(y)                           # [Cb, V]

    def verify_tokens(self, cache, block_tables, tokens, positions):
        from . import paged_kv
        pos = jnp.minimum(positions, self.max_seq - 1)
        x = self.p['emb'][tokens] + self.p['pos'][pos]        # [B, K, E]
        q, k, v = self._qkv(x)                                # [B, K, H, D]
        cache = paged_kv.write_tokens(cache, 0, block_tables, k, v,
                                      positions)
        out = paged_kv.attend_tokens(cache, 0, q, block_tables, positions)
        y = x + out.reshape(out.shape[0], out.shape[1], -1) @ self.p['wo']
        return cache, self._head(y)                           # [B, K, V]

    def reference_decode(self, prompt, max_new_tokens):
        """Greedy decode with NO cache (full forward each step): the
        independent oracle the KV-cache path is verified against."""
        toks = list(np.asarray(prompt, np.int32))
        for _ in range(int(max_new_tokens)):
            x = self.p['emb'][jnp.asarray(toks)] + self.p['pos'][:len(toks)]
            q, k, v = self._qkv(x)
            out = attend_prompt(q, k, v)
            y = x + out.reshape(len(toks), -1) @ self.p['wo']
            nxt = int(np.asarray(jnp.argmax(self._head(y)[-1])))
            toks.append(nxt)
            if self.eos_id is not None and nxt == self.eos_id:
                break
        return toks[len(np.asarray(prompt)):]
