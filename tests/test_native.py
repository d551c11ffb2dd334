"""Native (csrc/) components: prefetch ring, process workers, tokenizer."""
import threading

import numpy as np
import pytest

from paddle_tpu._native import available as native_available


def test_ring_ordered_multi_producer():
    from paddle_tpu._native.prefetch import make_ring
    r = make_ring(4, 1 << 18)
    n = 24

    def producer(seqs):
        for s in seqs:
            r.put([np.full((4, 4), s, np.float32)], s)

    ts = [threading.Thread(target=producer,
                           args=(list(range(i, n, 3)),)) for i in range(3)]
    for t in ts:
        t.start()
    got = 0
    while got < n:
        item = r.get()
        if item in (None, 'skip'):
            continue
        arrays, release = item
        assert arrays[0][0, 0] == got
        release()
        got += 1
    for t in ts:
        t.join()
    r.close()
    assert r.get() is None
    r.destroy()


@pytest.mark.skipif(not native_available(), reason="no native lib")
def test_ring_skip_marker():
    from paddle_tpu._native.prefetch import NativePrefetchRing
    r = NativePrefetchRing(4, 1 << 16)
    r.put([np.ones(3, np.float32)], 0)
    r.skip(1)
    r.put([np.zeros(3, np.float32)], 2)
    a, rel = r.get()
    assert a[0][0] == 1.0
    rel()
    assert r.get() == 'skip'
    a, rel = r.get()
    assert a[0][0] == 0.0
    rel()
    r.close()
    r.destroy()


def _blocked_producer(shm_name, seq):
    from multiprocessing import shared_memory
    from paddle_tpu._native.prefetch import NativePrefetchRing
    shm = shared_memory.SharedMemory(name=shm_name)
    ring = NativePrefetchRing.attach(shm.buf)
    ring.put([np.zeros(4, np.float32)], seq)     # ring is full: blocks


@pytest.mark.skipif(not native_available(), reason="no native lib")
def test_ring_survives_a_waiter_killed_while_blocked():
    """A worker SIGKILLed while it WAITS on the ring (full ring, consumer
    busy — where producers spend their time) must leave nothing behind
    that a later wake-up waits for. With a process-shared condvar the dead
    waiter never left its group and close()/release() blocked forever: the
    hang that cut the tier-1 run."""
    import multiprocessing as mp
    import os
    import signal
    import threading
    import time
    from multiprocessing import shared_memory
    from paddle_tpu._native.prefetch import NativePrefetchRing, block_bytes
    cap, slot = 2, 1 << 12
    shm = shared_memory.SharedMemory(create=True,
                                     size=block_bytes(cap, slot))
    try:
        ring = NativePrefetchRing(cap, slot, _buf=shm.buf)
        for seq in range(cap):
            ring.put([np.full(4, seq, np.float32)], seq)
        ctx = mp.get_context('fork')
        victims = [ctx.Process(target=_blocked_producer,
                               args=(shm.name, cap + i), daemon=True)
                   for i in range(2)]
        for v in victims:
            v.start()
        time.sleep(0.5)                  # both blocked in the native wait
        for v in victims:
            assert v.is_alive()
            os.kill(v.pid, signal.SIGKILL)
            v.join(5)

        def drain_and_close():
            for want in range(cap):
                arrays, release = ring.get(timeout_ms=2000)
                assert arrays[0][0] == want
                release()
            # rounds of "a LIVE waiter arrives, then someone wakes it": a
            # condvar stalls the waker as soon as its waiter groups switch
            # while a dead waiter still holds a reference to the old one
            for seq in range(cap, 4 * cap):
                got = []
                c = threading.Thread(
                    target=lambda: got.append(ring.get(timeout_ms=5000)),
                    daemon=True)
                c.start()
                time.sleep(0.1)          # the consumer is waiting now
                assert ring.put([np.zeros(4, np.float32)], seq)
                c.join(10)
                assert got and got[0] not in ('timeout', None)
                got[0][1]()
            assert ring.get(timeout_ms=50) == 'timeout'
            ring.close()
        t = threading.Thread(target=drain_and_close, daemon=True)
        t.start()
        t.join(20)
        assert not t.is_alive(), 'the ring waits for a dead process'
        ring.destroy()
        del ring
    finally:
        shm.close()
        shm.unlink()


@pytest.mark.skipif(not native_available(), reason="no native lib")
def test_dataloader_process_workers():
    import paddle_tpu as paddle
    from paddle_tpu.io import Dataset, DataLoader

    class D(Dataset):
        def __len__(self):
            return 32

        def __getitem__(self, i):
            return np.full((8,), i, np.float32), np.int64(i % 2)

    dl = DataLoader(D(), batch_size=4, num_workers=2, shuffle=False)
    seen = []
    for x, y in dl:
        assert x.shape == [4, 8]
        seen.append(float(x.numpy()[0, 0]))
    assert seen == [0.0, 4.0, 8.0, 12.0, 16.0, 20.0, 24.0, 28.0]


def test_tokenizer_native_matches_python():
    from paddle_tpu._native.tokenizer import Tokenizer
    vocab = {'[UNK]': 0, 'the': 1, 'cat': 2, '.': 3,
             'un': 4, '##aff': 5, '##able': 6, 'run': 7, '##ning': 8}
    for wordpiece in (False, True):
        t = Tokenizer(vocab, wordpiece=wordpiece)
        p = Tokenizer(vocab, wordpiece=wordpiece)
        p._cvocab = None   # force python fallback
        for text in ('The cat.', 'unaffable running cat', 'zzz unknown!'):
            np.testing.assert_array_equal(t.encode(text), p.encode(text))
    t = Tokenizer(vocab, wordpiece=True)
    ids, lens = t.encode_batch(['the cat .', 'unaffable'], max_len=8)
    assert ids.shape == (2, 8) and lens.tolist() == [3, 3]
