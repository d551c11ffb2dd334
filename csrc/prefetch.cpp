// Native prefetch ring for the DataLoader hot path.
//
// Parity target: the reference's C++ reader stack — BlockingQueue +
// buffered readers (paddle/fluid/operators/reader/blocking_queue.h,
// buffered_reader.cc) and the shared-memory tensor transport used by its
// multiprocess DataLoader (core._convert_to_shared_memory). TPU-first
// equivalent: ONE contiguous memory block (private or POSIX shm) laid out as
//
//   [Hdr | state[capacity] | size[capacity] | slots (aligned)...]
//
// with a PROCESS_SHARED mutex and a futex word in the header, so worker
// PROCESSES serialize numpy batches straight into shared slots — no pickle,
// no pipe — and the consumer maps them zero-copy. Slots are acquired by
// SEQUENCE NUMBER (pring_acquire_write_seq), so batch order is preserved
// end-to-end even with racing workers. All blocking waits run in C with the
// GIL released by ctypes.
//
// A participant can be SIGKILLed at any instruction (OOM killer, preemption,
// the fault-injection tests), so nothing here may depend on a dead process:
// the mutex is ROBUST (the next locker inherits it, EOWNERDEAD), and waiting
// is a bare futex on a generation counter — the kernel keeps no record of a
// waiter that died. A process-shared pthread condvar does: a waiter killed
// inside pthread_cond_wait never leaves its group, and a later
// signal/broadcast blocks forever waiting for it (that hung
// ProcessWorkerPool.shutdown, and with it the whole test run).

#include <errno.h>
#include <limits.h>
#include <linux/futex.h>
#include <pthread.h>
#include <stdint.h>
#include <string.h>
#include <sys/syscall.h>
#include <time.h>
#include <unistd.h>

namespace {

enum SlotState : int32_t { FREE = 0, WRITING = 1, READY = 2, READING = 3 };

struct Hdr {
  uint64_t magic;
  int64_t capacity;
  int64_t slot_bytes;      // aligned payload bytes per slot
  int64_t slots_offset;    // byte offset of slot 0 from block start
  int64_t next_write_seq;  // next sequence number allowed to acquire
  int64_t read_seq;        // next sequence number the consumer will read
  int32_t closed;
  uint32_t gen;            // futex word: bumped (under mu) on every change
  pthread_mutex_t mu;
};

constexpr uint64_t kMagic = 0x70616464726e6701ULL;  // "paddrng\1"
constexpr int64_t kAlign = 4096;

inline int32_t* states(Hdr* h) {
  return reinterpret_cast<int32_t*>(reinterpret_cast<char*>(h) + sizeof(Hdr));
}
inline int64_t* sizes(Hdr* h) {
  return reinterpret_cast<int64_t*>(
      reinterpret_cast<char*>(states(h)) + sizeof(int32_t) * h->capacity);
}
inline char* slot(Hdr* h, int64_t idx) {
  return reinterpret_cast<char*>(h) + h->slots_offset + idx * h->slot_bytes;
}

inline void lock(Hdr* h) {
  if (pthread_mutex_lock(&h->mu) == EOWNERDEAD) {
    // the previous owner died inside a critical section: every field it
    // guards is a single word, so the state is usable as it stands
    pthread_mutex_consistent(&h->mu);
  }
}

// Publish a change made under mu: unlock, then wake every waiter.
inline void unlock_and_wake(Hdr* h) {
  __atomic_add_fetch(&h->gen, 1, __ATOMIC_SEQ_CST);
  pthread_mutex_unlock(&h->mu);
  syscall(SYS_futex, &h->gen, FUTEX_WAKE, INT_MAX, nullptr, nullptr, 0);
}

// Called with mu held: release it, sleep until the generation moves on from
// what it was under the lock (or `ts` elapses; nullptr = no limit), retake
// mu. A change between the unlock and the wait is not lost: FUTEX_WAIT
// returns at once when the word no longer holds `seen`.
inline void wait_change(Hdr* h, const struct timespec* ts) {
  uint32_t seen = __atomic_load_n(&h->gen, __ATOMIC_SEQ_CST);
  pthread_mutex_unlock(&h->mu);
  syscall(SYS_futex, &h->gen, FUTEX_WAIT, seen, ts, nullptr, 0);
  lock(h);
}

}  // namespace

extern "C" {

// Bytes needed for a ring block with this capacity/slot size.
int64_t pring_block_bytes(int64_t capacity, int64_t slot_bytes) {
  slot_bytes = (slot_bytes + kAlign - 1) / kAlign * kAlign;
  int64_t hdr = sizeof(Hdr) + capacity * (sizeof(int32_t) + sizeof(int64_t));
  hdr = (hdr + kAlign - 1) / kAlign * kAlign;
  return hdr + capacity * slot_bytes;
}

// Initialize a ring inside caller-provided memory (malloc'd or shm mmap).
// Returns 0 on success.
int pring_init(void* mem, int64_t capacity, int64_t slot_bytes) {
  if (!mem || capacity <= 0 || slot_bytes <= 0) return -1;
  Hdr* h = static_cast<Hdr*>(mem);
  h->capacity = capacity;
  h->slot_bytes = (slot_bytes + kAlign - 1) / kAlign * kAlign;
  int64_t hdr = sizeof(Hdr) + capacity * (sizeof(int32_t) + sizeof(int64_t));
  h->slots_offset = (hdr + kAlign - 1) / kAlign * kAlign;
  h->next_write_seq = 0;
  h->read_seq = 0;
  h->closed = 0;
  h->gen = 0;
  for (int64_t i = 0; i < capacity; ++i) {
    states(h)[i] = FREE;
    sizes(h)[i] = 0;
  }
  pthread_mutexattr_t ma;
  pthread_mutexattr_init(&ma);
  pthread_mutexattr_setpshared(&ma, PTHREAD_PROCESS_SHARED);
  pthread_mutexattr_setrobust(&ma, PTHREAD_MUTEX_ROBUST);
  if (pthread_mutex_init(&h->mu, &ma) != 0) return -2;
  h->magic = kMagic;
  return 0;
}

int pring_valid(void* mem) {
  return mem && static_cast<Hdr*>(mem)->magic == kMagic;
}

int64_t pring_slot_bytes(void* mem) {
  return static_cast<Hdr*>(mem)->slot_bytes;
}

// Block until sequence number `seq` may write (all earlier seqs have
// acquired their slots and slot seq%capacity is FREE). Returns the slot
// index, or -1 if closed.
int64_t pring_acquire_write_seq(void* mem, int64_t seq) {
  Hdr* h = static_cast<Hdr*>(mem);
  lock(h);
  int64_t idx = seq % h->capacity;
  while (!h->closed &&
         (h->next_write_seq != seq || states(h)[idx] != FREE)) {
    wait_change(h, nullptr);
  }
  if (h->closed) {
    pthread_mutex_unlock(&h->mu);
    return -1;
  }
  h->next_write_seq = seq + 1;
  states(h)[idx] = WRITING;
  unlock_and_wake(h);
  return idx;
}

void* pring_slot_ptr(void* mem, int64_t idx) {
  return slot(static_cast<Hdr*>(mem), idx);
}

void pring_commit_write(void* mem, int64_t idx, int64_t size) {
  Hdr* h = static_cast<Hdr*>(mem);
  lock(h);
  sizes(h)[idx] = size;
  states(h)[idx] = READY;
  unlock_and_wake(h);
}

// Abort = commit an empty (size 0) payload: the consumer skips it. Marking
// the slot FREE instead would deadlock the in-order reader waiting on the
// aborted sequence number.
void pring_abort_write(void* mem, int64_t idx) {
  Hdr* h = static_cast<Hdr*>(mem);
  lock(h);
  sizes(h)[idx] = 0;
  states(h)[idx] = READY;
  unlock_and_wake(h);
}

// Block until the next-in-order batch is READY; returns slot index and
// fills *size; -1 when the ring is closed and fully drained; -2 on timeout
// (timeout_ms < 0 waits forever). Timeouts let the consumer poll producer
// liveness instead of hanging on a crashed worker's unclaimed sequence.
int64_t pring_acquire_read_timeout(void* mem, int64_t* size,
                                   int64_t timeout_ms) {
  Hdr* h = static_cast<Hdr*>(mem);
  struct timespec deadline;
  if (timeout_ms >= 0) {
    clock_gettime(CLOCK_MONOTONIC, &deadline);
    deadline.tv_sec += timeout_ms / 1000;
    deadline.tv_nsec += (timeout_ms % 1000) * 1000000L;
    if (deadline.tv_nsec >= 1000000000L) {
      deadline.tv_sec += 1;
      deadline.tv_nsec -= 1000000000L;
    }
  }
  lock(h);
  int64_t idx = h->read_seq % h->capacity;
  while (true) {
    if (states(h)[idx] == READY) break;
    // closed and no writer has claimed (or will claim) this seq -> drained
    if (h->closed && h->read_seq >= h->next_write_seq &&
        states(h)[idx] == FREE) {
      pthread_mutex_unlock(&h->mu);
      return -1;
    }
    if (timeout_ms < 0) {
      wait_change(h, nullptr);
    } else {
      // FUTEX_WAIT takes a RELATIVE timeout: what is left of the deadline
      struct timespec now, left;
      clock_gettime(CLOCK_MONOTONIC, &now);
      left.tv_sec = deadline.tv_sec - now.tv_sec;
      left.tv_nsec = deadline.tv_nsec - now.tv_nsec;
      if (left.tv_nsec < 0) {
        left.tv_sec -= 1;
        left.tv_nsec += 1000000000L;
      }
      if (left.tv_sec < 0) {
        pthread_mutex_unlock(&h->mu);
        return -2;
      }
      wait_change(h, &left);
    }
  }
  h->read_seq += 1;
  states(h)[idx] = READING;
  *size = sizes(h)[idx];
  pthread_mutex_unlock(&h->mu);
  return idx;
}

int64_t pring_acquire_read(void* mem, int64_t* size) {
  return pring_acquire_read_timeout(mem, size, -1);
}

void pring_release_read(void* mem, int64_t idx) {
  Hdr* h = static_cast<Hdr*>(mem);
  lock(h);
  states(h)[idx] = FREE;
  unlock_and_wake(h);
}

void pring_close(void* mem) {
  Hdr* h = static_cast<Hdr*>(mem);
  lock(h);
  h->closed = 1;
  unlock_and_wake(h);
}

}  // extern "C"
