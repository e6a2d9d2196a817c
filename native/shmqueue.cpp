// Shared-memory ring buffer: the zero-copy feed path between the engine's
// feeder task and the training process on one host.
//
// TPU-native replacement for the reference's per-record pickled
// multiprocessing queues (the documented hot-loop bottleneck,
// TFSparkNode.py:480-482 ↔ TFNode.py:265-287): a single-producer /
// single-consumer byte ring in POSIX shared memory carrying *batches*
// (e.g. serialized record chunks or raw tensor blocks) with no syscalls
// on the fast path.
//
// Layout: Header | data[capacity]
//   head: next write offset (producer-owned), tail: next read offset
//   (consumer-owned); both are free-running uint64 counters mod capacity.
//   Each message: uint32 len | 4 unused | payload | padding to 8 bytes, so
//   a payload starts 8-byte aligned (the header is 64 bytes).  A len word
//   of kSkip means: nothing more on this lap, the message is at offset 0.
//   closed: producer sets when done (consumer drains then sees EOF).

#include <algorithm>
#include <atomic>
#include <cerrno>
#include <cstdint>
#include <string>
#include <cstdio>
#include <cstring>
#include <ctime>
#include <fcntl.h>
#include <sys/mman.h>
#include <sys/stat.h>
#include <unistd.h>

namespace {

constexpr uint64_t kMagic = 0x54464f53514d5632ull;  // "TFOSQMV2"
constexpr uint32_t kSkip = 0xffffffffu;

struct Header {
  uint64_t magic;
  uint64_t capacity;
  std::atomic<uint64_t> head;
  std::atomic<uint64_t> tail;
  std::atomic<uint32_t> closed;
  uint8_t _pad[28];  // to 64 bytes: the data starts on a cache line
};
static_assert(sizeof(Header) == 64, "ring header layout");

constexpr uint64_t kMsgHeader = 8;

struct Queue {
  Header* h;
  uint8_t* data;
  size_t map_len;
  std::string name;
  int fd;  // kept for populate()
  bool owner;
  uint64_t reserved;     // bytes the open reservation will publish
  uint64_t unpopulated;  // bytes of this mapping's first lap still to write
  uint64_t wait_ns;      // time shq_reserve has spent waiting for room
};

inline uint64_t align8(uint64_t n) { return (n + 7) & ~7ull; }

void sleep_us(unsigned us) {
  struct timespec ts {0, (long)us * 1000};
  nanosleep(&ts, nullptr);
}

// One step of every wait on the ring: 50 us for the first 2 ms, then 500.
void back_off(int* waited_us) {
  unsigned us = *waited_us < 2000 ? 50 : 500;
  sleep_us(us);
  *waited_us += us;
}

uint64_t now_ns() {
  struct timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return (uint64_t)ts.tv_sec * 1000000000ull + (uint64_t)ts.tv_nsec;
}

// The message at `*tail` (free-running): its length; a skip word moves
// `*tail` on to the lap's start first.
uint32_t next_message(const Queue* q, uint64_t* tail) {
  uint64_t cap = q->h->capacity;
  uint32_t len32;
  memcpy(&len32, q->data + *tail % cap, 4);
  if (len32 == kSkip) {
    *tail += cap - *tail % cap;
    memcpy(&len32, q->data, 4);
  }
  return len32;
}

// Every feeder task maps the ring afresh, and a page first touched
// through a new mapping costs a fault each: on the chip's host six times
// the copy itself (PERF.md section 6, PR26).  So on a mapping's first lap
// the pages of a reservation are mapped again in place with MAP_POPULATE,
// which faults them all in with one call (MADV_POPULATE_WRITE would say
// the same, where the kernel has it; that host's does not).
void populate(Queue* q, uint64_t off, uint64_t need) {
  if (!q->unpopulated) return;
  static const uintptr_t page = (uintptr_t)sysconf(_SC_PAGESIZE);
  uintptr_t base = (uintptr_t)q->h;
  uint64_t first = std::min(need, q->h->capacity - off);
  const uint64_t parts[2][2] = {{off, first}, {0, need - first}};
  for (auto& part : parts) {
    if (!part[1]) continue;
    // whole pages, and never the one the header's atomics live in
    uintptr_t lo = std::max((uintptr_t)(q->data + part[0]) & ~(page - 1),
                            base + page);
    uintptr_t hi = std::min(
        ((uintptr_t)(q->data + part[0] + part[1]) + page - 1) & ~(page - 1),
        base + q->map_len);
    if (hi > lo &&
        mmap((void*)lo, hi - lo, PROT_READ | PROT_WRITE,
             MAP_SHARED | MAP_FIXED | MAP_POPULATE, q->fd,
             (off_t)(lo - base)) == MAP_FAILED) {
      q->unpopulated = 0;  // the writes fault one by one, as before
      return;
    }
  }
  q->unpopulated -= std::min(q->unpopulated, need);
}

}  // namespace

extern "C" {

Queue* shq_create(const char* name, uint64_t capacity) {
  capacity = align8(capacity);
  shm_unlink(name);  // stale segment from a crashed run
  int fd = shm_open(name, O_CREAT | O_EXCL | O_RDWR, 0600);
  if (fd < 0) return nullptr;
  size_t len = sizeof(Header) + capacity;
  if (ftruncate(fd, (off_t)len) != 0) {
    close(fd);
    shm_unlink(name);
    return nullptr;
  }
  void* mem = mmap(nullptr, len, PROT_READ | PROT_WRITE, MAP_SHARED, fd, 0);
  if (mem == MAP_FAILED) {
    close(fd);
    shm_unlink(name);
    return nullptr;
  }
  auto* h = new (mem) Header();
  h->capacity = capacity;
  h->head.store(0);
  h->tail.store(0);
  h->closed.store(0);
  h->magic = kMagic;  // published last
  return new Queue{h, (uint8_t*)mem + sizeof(Header), len, name, fd, true, 0,
                   capacity, 0};
}

Queue* shq_open(const char* name, int timeout_ms) {
  int fd = -1;
  for (int waited = 0;; waited += 10) {
    fd = shm_open(name, O_RDWR, 0600);
    if (fd >= 0) break;
    if (waited >= timeout_ms) return nullptr;
    sleep_us(10000);
  }
  struct stat st;
  if (fstat(fd, &st) != 0 || (size_t)st.st_size < sizeof(Header)) {
    close(fd);
    return nullptr;
  }
  void* mem = mmap(nullptr, (size_t)st.st_size, PROT_READ | PROT_WRITE,
                   MAP_SHARED, fd, 0);
  if (mem == MAP_FAILED) {
    close(fd);
    return nullptr;
  }
  auto* h = (Header*)mem;
  for (int waited = 0; h->magic != kMagic; waited += 1) {
    if (waited > 1000) {
      munmap(mem, (size_t)st.st_size);
      close(fd);
      return nullptr;
    }
    sleep_us(1000);
  }
  return new Queue{h, (uint8_t*)mem + sizeof(Header), (size_t)st.st_size,
                   name, fd, false, 0, h->capacity, 0};
}

// THE producer primitive.  Wait for room for one message of `len` payload
// bytes and reserve it: returns the payload's offset in the data region
// (shq_data) for the caller to write at, then shq_commit publishes it.
// -1 timeout; -2 closed; -3 message larger than the ring.  Nothing is
// visible to the consumer before the commit, and a reservation that is
// never committed (shq_drop, or simply the next shq_reserve) publishes
// nothing.
//
// A message of at most half the ring is CONTIGUOUS, so the caller can
// hand out views of it: where it would straddle the ring's end, a skip
// word is written there and the message starts at offset 0; the commit
// publishes both.  Half the ring is the bound because the skipped tail
// is shorter than the message, so an empty ring always has room for
// both.  A larger message wraps and is written in two parts.
int64_t shq_reserve(Queue* q, uint64_t len, int timeout_ms) {
  Header* h = q->h;
  uint64_t need = align8(kMsgHeader + len);
  if (len >= kSkip || need + 8 > h->capacity) return -3;
  bool contiguous = need * 2 <= h->capacity;
  q->reserved = 0;
  int waited_us = 0;
  uint64_t t_wait = 0;  // when the wait for room began, if there was one
  for (;;) {
    if (h->closed.load(std::memory_order_acquire)) return -2;
    uint64_t head = h->head.load(std::memory_order_relaxed);
    uint64_t tail = h->tail.load(std::memory_order_acquire);
    uint64_t off = head % h->capacity;
    uint64_t skip =
        contiguous && off + need > h->capacity ? h->capacity - off : 0;
    bool room = head + skip + need - tail <= h->capacity - 8;
    bool timed_out = timeout_ms >= 0 && waited_us / 1000 >= timeout_ms;
    if (t_wait && (room || timed_out)) q->wait_ns += now_ns() - t_wait;
    if (room) {
      uint32_t len32 = (uint32_t)len;
      if (skip) {
        // header words never wrap (8-byte alignment)
        memcpy(q->data + off, &kSkip, 4);
        off = 0;
      }
      memcpy(q->data + off, &len32, 4);
      q->reserved = skip + need;
      populate(q, off, need);
      return (int64_t)((off + kMsgHeader) % h->capacity);
    }
    if (timed_out) return -1;
    if (!t_wait) t_wait = now_ns();
    back_off(&waited_us);
  }
}

// Publish the reservation (a no-op without one).  Returns the ring's
// free-running head after it: the position shq_wait_tail waits for.
uint64_t shq_commit(Queue* q) {
  Header* h = q->h;
  uint64_t head = h->head.load(std::memory_order_relaxed) + q->reserved;
  q->reserved = 0;
  h->head.store(head, std::memory_order_release);
  return head;
}

void shq_drop(Queue* q) { q->reserved = 0; }

// Nanoseconds this endpoint's reservations have waited for room.
uint64_t shq_wait_ns(Queue* q) { return q->wait_ns; }

// Wait until the consumer has consumed everything up to the free-running
// position `pos` (a value shq_commit returned): 0 done, -1 timeout.
// Bytes a later producer wrote behind `pos` do not hold it up.
int shq_wait_tail(Queue* q, uint64_t pos, int timeout_ms) {
  Header* h = q->h;
  int waited_us = 0;
  for (;;) {
    if (h->tail.load(std::memory_order_acquire) >= pos) return 0;
    if (timeout_ms >= 0 && waited_us / 1000 >= timeout_ms) return -1;
    back_off(&waited_us);
  }
}

// Wait for the next message and return its length WITHOUT consuming it
// (-1 timeout, -2 EOF).  Pair with shq_pop_into to copy the payload
// directly into a caller-owned buffer: one copy on the consumer side.
int64_t shq_peek_len(Queue* q, int timeout_ms) {
  Header* h = q->h;
  int waited_us = 0;
  for (;;) {
    uint64_t tail = h->tail.load(std::memory_order_relaxed);
    uint64_t head = h->head.load(std::memory_order_acquire);
    if (head != tail) return (int64_t)next_message(q, &tail);
    if (h->closed.load(std::memory_order_acquire)) return -2;
    if (timeout_ms >= 0 && waited_us / 1000 >= timeout_ms) return -1;
    back_off(&waited_us);
  }
}

// Copy the pending message's payload into dst (size from shq_peek_len)
// and consume it.  Returns the length, or -1 if no message is pending
// (misuse: call only after a successful shq_peek_len).
int64_t shq_pop_into(Queue* q, uint8_t* dst) {
  Header* h = q->h;
  uint64_t tail = h->tail.load(std::memory_order_relaxed);
  uint64_t head = h->head.load(std::memory_order_acquire);
  if (head == tail) return -1;
  uint32_t len32 = next_message(q, &tail);
  uint64_t poff = (tail + kMsgHeader) % h->capacity;
  uint64_t first = std::min((uint64_t)len32, h->capacity - poff);
  memcpy(dst, q->data + poff, first);
  if (first < len32) memcpy(dst + first, q->data, len32 - first);
  h->tail.store(tail + align8(kMsgHeader + len32),
                std::memory_order_release);
  return (int64_t)len32;
}

uint8_t* shq_data(Queue* q) { return q->data; }

uint64_t shq_capacity(Queue* q) { return q->h->capacity; }

void shq_close_write(Queue* q) {
  q->h->closed.store(1, std::memory_order_release);
}

uint64_t shq_size(Queue* q) {
  return q->h->head.load() - q->h->tail.load();
}

void shq_free(Queue* q) {
  bool owner = q->owner;
  std::string name = q->name;
  munmap((void*)((uint8_t*)q->h), q->map_len);
  close(q->fd);
  if (owner) shm_unlink(name.c_str());
  delete q;
}

}  // extern "C"
