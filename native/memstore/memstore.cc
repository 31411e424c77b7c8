// memstore implementation — see memstore.h for the design rationale and the
// mapping onto the reference's mem_etcd (reference mem_etcd/src/*.rs).
//
// Deliberate redesigns vs the reference (documented, not accidental):
//  * One global ordered index instead of per-Kind B-trees: cross-prefix
//    ranges work (the reference errors on them, store.rs:590-675); the
//    per-Kind prefix_split survives in the WAL file layout and stats.
//  * Watch events enqueue inside the write critical section, once a frame
//    (a batch or a single set), so revision order is structural; no notify
//    thread / re-ordering heap (reference store.rs:444-533 needs both).
//  * A key and a write are each one reference-counted allocation that the
//    indexes, the log, the WAL queue, the watch queues and the polls share.
//  * Tombstones are garbage-collected at compaction (the reference leaves
//    this as a TODO, store.rs:832).
//  * Values live at the compact revision are preserved in a per-key base
//    slot so reads at rev >= compact_rev stay correct even for keys whose
//    last write predates compaction.

#include "memstore.h"

#include <dirent.h>
#include <fcntl.h>
#include <sys/stat.h>
#include <sys/uio.h>
#include <unistd.h>

#include <chrono>

#include <algorithm>
#include <atomic>
#include <condition_variable>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <deque>
#include <map>
#include <memory>
#include <mutex>
#include <new>
#include <queue>
#include <shared_mutex>
#include <string>
#include <string_view>
#include <thread>
#include <unordered_map>
#include <vector>

namespace {

inline int64_t now_ns() {
  return std::chrono::duration_cast<std::chrono::nanoseconds>(
             std::chrono::steady_clock::now().time_since_epoch())
      .count();
}

// ---- records --------------------------------------------------------------
// A key and a write are each ONE allocation, made when they enter the store
// and shared by reference from then on: the two indexes view the key's
// bytes, and the item, the MVCC log, the WAL queue, every watcher's queue
// and the polls hold the same record.  The count lives in the allocation
// (intrusive), so a reference is one pointer and taking one is one atomic
// add.  A record is immutable once its write is committed.

template <typename T>
class Ref {
 public:
  Ref() = default;
  static Ref adopt(T* p) { return Ref(p); }  // takes over the caller's count
  static Ref share(T* p) {                   // one more reference to *p
    if (p) p->rc.fetch_add(1, std::memory_order_relaxed);
    return Ref(p);
  }
  Ref(const Ref& o) : Ref(share(o.p_)) {}
  Ref(Ref&& o) noexcept : p_(o.p_) { o.p_ = nullptr; }
  Ref& operator=(Ref o) noexcept {
    std::swap(p_, o.p_);
    return *this;
  }
  ~Ref() {
    if (p_ && p_->rc.fetch_sub(1, std::memory_order_acq_rel) == 1)
      T::destroy(p_);
  }
  T* get() const { return p_; }
  T* operator->() const { return p_; }
  T& operator*() const { return *p_; }
  explicit operator bool() const { return p_ != nullptr; }

 private:
  explicit Ref(T* p) : p_(p) {}
  T* p_ = nullptr;
};

struct Key {
  std::atomic<uint32_t> rc{1};
  uint32_t len = 0;
  char data[];  // len bytes

  std::string_view view() const { return {data, len}; }
  static Ref<Key> make(std::string_view k) {
    Key* p = new (malloc(sizeof(Key) + k.size())) Key();
    p->len = static_cast<uint32_t>(k.size());
    memcpy(p->data, k.data(), k.size());
    return Ref<Key>::adopt(p);
  }
  static void destroy(Key* p) { free(p); }
};

// One write of one key: a value, or a tombstone (del: no value, and
// create_rev / version / lease 0, as etcd reports a delete).
struct Rec {
  std::atomic<uint32_t> rc{1};
  uint32_t vlen = 0;
  bool del = false;
  int64_t mod_rev = 0, create_rev = 0, version = 0, lease = 0;
  Ref<Key> key;
  char val[];  // vlen bytes; the writer fills them before it commits

  std::string_view value() const { return {val, vlen}; }
  static Ref<Rec> make(Ref<Key> key, size_t vlen, bool del = false) {
    Rec* p = new (malloc(sizeof(Rec) + vlen)) Rec();
    p->vlen = static_cast<uint32_t>(vlen);
    p->del = del;
    p->key = std::move(key);
    return Ref<Rec>::adopt(p);
  }
  static void destroy(Rec* p) {
    p->~Rec();
    free(p);
  }
};

using RecRef = Ref<Rec>;

// ---- prefix_split ---------------------------------------------------------
// /registry/<kind>/...          -> /registry/<kind>/
// /registry/<group.with.dot>/<kind>/... -> /registry/<group>/<kind>/
// (reference store.rs:836-863: Kubernetes never ranges across Kinds).
// *closed says that every key that starts with the result splits to it too,
// so a run of keys under it need not be split again.
std::string_view prefix_split(std::string_view key, bool* closed) {
  *closed = false;
  if (key.empty() || key[0] != '/') return key;
  size_t s1 = key.find('/', 1);
  if (s1 == std::string_view::npos) return key;
  size_t s2 = key.find('/', s1 + 1);
  if (s2 == std::string_view::npos) return key;
  // second path component (between s1 and s2)
  const bool group = key.find('.', s1 + 1) < s2;
  if (group) {
    size_t s3 = key.find('/', s2 + 1);
    if (s3 != std::string_view::npos) {
      *closed = true;
      return key.substr(0, s3 + 1);
    }
  }
  *closed = !group;
  return key.substr(0, s2 + 1);
}

// ---- serialization --------------------------------------------------------

void put_u32(std::string& b, uint32_t v) {
  b.append(reinterpret_cast<const char*>(&v), 4);
}
void put_u8(std::string& b, uint8_t v) { b.push_back(static_cast<char>(v)); }
void put_i64(std::string& b, int64_t v) {
  b.append(reinterpret_cast<const char*>(&v), 8);
}

void put_kv(std::string& b, const Rec& r, bool keys_only = false) {
  const bool hv = !r.del && !keys_only;
  put_u32(b, r.key->len);
  put_u32(b, hv ? r.vlen : 0);
  put_i64(b, r.create_rev);
  put_i64(b, r.mod_rev);
  put_i64(b, r.version);
  put_i64(b, r.lease);
  b.append(r.key->view());
  if (hv) b.append(r.value());
}

uint8_t* to_malloc(const std::string& b, size_t* len_out) {
  uint8_t* p = static_cast<uint8_t*>(malloc(b.size() ? b.size() : 1));
  memcpy(p, b.data(), b.size());
  *len_out = b.size();
  return p;
}

// ---- core structures ------------------------------------------------------

// The revisions that touched one key, ascending.  A pod's life is two of
// them (create, bind), which live in the item itself.
class RevList {
 public:
  RevList() = default;
  RevList(const RevList&) = delete;
  RevList& operator=(const RevList&) = delete;
  ~RevList() {
    if (p_ != inl_) free(p_);
  }
  void push_back(int64_t rev) {
    if (n_ == cap_) {
      cap_ *= 2;
      auto* q = static_cast<int64_t*>(malloc(sizeof(int64_t) * cap_));
      memcpy(q, p_, sizeof(int64_t) * n_);
      if (p_ != inl_) free(p_);
      p_ = q;
    }
    p_[n_++] = rev;
  }
  const int64_t* begin() const { return p_; }
  const int64_t* end() const { return p_ + n_; }

 private:
  int64_t inl_[2];
  int64_t* p_ = inl_;
  uint32_t n_ = 0, cap_ = 2;
};

struct TreeItem {
  Ref<Key> key;
  RevList revs;   // every revision that touched this key
  RecRef latest;  // the last write, a tombstone included
  // Value live at the compact revision when history below it was dropped.
  RecRef base;

  bool present() const { return latest && !latest->del; }
};

struct RevEntry {  // one revision in the global MVCC log
  TreeItem* item = nullptr;
  RecRef rec;
};

// What a watcher's queue holds: the write, and for a watcher that asked
// for prev_kv the write it replaced.
struct Event {
  RecRef rec;
  RecRef prev;
};

constexpr size_t kDefaultWatcherQueueCap = 10000;  // reference store.rs:27

struct Watcher {
  int64_t id = 0;
  size_t queue_cap = kDefaultWatcherQueueCap;
  std::string start, end;  // [start, end), or start alone, or from start on
  bool single = false;
  bool to_infinity = false;
  bool want_prev = false;
  int64_t min_rev = 0;  // suppress live events below this revision
  std::mutex m;
  std::condition_variable cv;
  std::deque<Event> q;
  int64_t dropped = 0;
  bool canceled = false;

  bool matches(std::string_view key) const {
    if (single) return key == start;
    if (key < start) return false;
    return to_infinity || key < end;
  }

  // Up to max_events from the head of the queue, after waiting up to
  // timeout_ms for the first.  A poll that takes all there is takes the
  // queue itself.
  std::deque<Event> take(int max_events, int timeout_ms, bool* canceled_out) {
    std::deque<Event> out;
    std::unique_lock<std::mutex> g(m);
    if (q.empty() && timeout_ms > 0 && !canceled)
      cv.wait_for(g, std::chrono::milliseconds(timeout_ms),
                  [&] { return !q.empty() || canceled; });
    *canceled_out = canceled;
    if (max_events > 0 && q.size() <= static_cast<size_t>(max_events)) {
      out.swap(q);
    } else {
      for (int i = 0; i < max_events; i++) {
        out.push_back(std::move(q.front()));
        q.pop_front();
      }
    }
    return out;
  }
};

// ---- WAL ------------------------------------------------------------------
// Per-prefix append-only files, background writer batching into writev,
// modes none/buffered/fsync, boot-time merge-replay by revision
// (reference mem_etcd/src/wal.rs:62-299).

struct WalRec {
  int fd = -1;
  RecRef rec;
};

constexpr uint32_t kDeleteMarker = 0xFFFFFFFFu;

std::string hex_encode(std::string_view s) {
  static const char* d = "0123456789abcdef";
  std::string o;
  o.reserve(s.size() * 2);
  for (unsigned char c : s) {
    o.push_back(d[c >> 4]);
    o.push_back(d[c & 15]);
  }
  return o;
}

class Wal {
 public:
  Wal(std::string dir, int mode) : dir_(std::move(dir)), mode_(mode) {
    writer_ = std::thread([this] { Run(); });
  }

  ~Wal() {
    {
      std::lock_guard<std::mutex> g(qm_);
      stop_ = true;
    }
    qcv_.notify_all();
    writer_.join();
    for (auto& [prefix, fd] : fds_)
      if (fd >= 0) close(fd);
  }

  int FdFor(const std::string& prefix) {
    std::lock_guard<std::mutex> g(fd_mu_);
    auto it = fds_.find(prefix);
    if (it != fds_.end()) return it->second;
    std::string path = dir_ + "/prefix_" + hex_encode(prefix) + ".wal";
    int fd = open(path.c_str(), O_WRONLY | O_CREAT | O_APPEND, 0644);
    fds_[prefix] = fd;
    return fd;
  }

  void Append(int fd, RecRef rec) {
    {
      // Contention-metered (reference metrics.rs:78-94): the queue mutex
      // is shared with the writer thread's drain, the one lock a write
      // can block on outside the store mutex.
      std::unique_lock<std::mutex> g(qm_, std::defer_lock);
      if (!g.try_lock()) {
        int64_t t0 = now_ns();
        g.lock();
        append_wait_ns.fetch_add(now_ns() - t0, std::memory_order_relaxed);
      }
      append_count.fetch_add(1, std::memory_order_relaxed);
      last_enqueued_ = rec->mod_rev;
      q_.push_back(WalRec{fd, std::move(rec)});
    }
    qcv_.notify_one();
  }

  std::atomic<int64_t> append_count{0};
  std::atomic<int64_t> append_wait_ns{0};

  void WaitPersisted(int64_t rev) {
    std::unique_lock<std::mutex> g(pm_);
    pcv_.wait(g, [&] { return persisted_ >= rev || io_error_; });
  }

  int Sync() {
    int64_t target;
    {
      std::lock_guard<std::mutex> g(qm_);
      target = last_enqueued_;
    }
    WaitPersisted(target);
    {
      std::lock_guard<std::mutex> g(fd_mu_);
      for (auto& [prefix, fd] : fds_)
        if (fd >= 0 && fsync(fd) != 0) return MS_ERR_IO;
    }
    return io_error_ ? MS_ERR_IO : MS_OK;
  }

  bool fsync_mode() const { return mode_ == MS_WAL_FSYNC; }
  int mode() const { return mode_; }
  int64_t persisted_revision() {
    std::lock_guard<std::mutex> g(pm_);
    return persisted_;
  }
  bool io_error() {
    std::lock_guard<std::mutex> g(pm_);
    return io_error_;
  }

 private:
  void Run() {
    std::vector<WalRec> batch;
    for (;;) {
      {
        std::unique_lock<std::mutex> g(qm_);
        qcv_.wait(g, [&] { return stop_ || !q_.empty(); });
        if (q_.empty() && stop_) return;
        // Drain up to ~16 KiB worth or the whole queue, whichever is
        // smaller (reference wal.rs:173-248 batches 16 KiB / 500 us).
        size_t bytes = 0;
        while (!q_.empty() && bytes < (16u << 10)) {
          bytes += q_.front().rec->key->len + q_.front().rec->vlen + 16;
          batch.push_back(std::move(q_.front()));
          q_.pop_front();
        }
      }
      WriteBatch(batch);
      batch.clear();
    }
  }

  void WriteBatch(std::vector<WalRec>& batch) {
    if (batch.empty()) return;
    // Group contiguous records per fd into one buffered write.
    std::unordered_map<int, std::string> bufs;
    int64_t max_rev = 0;
    for (auto& w : batch) {
      const Rec& r = *w.rec;
      std::string& b = bufs[w.fd];
      uint64_t rev = static_cast<uint64_t>(r.mod_rev);
      b.append(reinterpret_cast<const char*>(&rev), 8);
      put_u32(b, r.key->len);
      put_u32(b, r.del ? kDeleteMarker : r.vlen);
      b.append(r.key->view());
      if (!r.del) b.append(r.value());
      max_rev = std::max(max_rev, r.mod_rev);
    }
    bool err = false;
    for (auto& [fd, buf] : bufs) {
      if (fd < 0) continue;
      const char* p = buf.data();
      size_t n = buf.size();
      while (n > 0) {
        ssize_t w = write(fd, p, n);
        if (w < 0) {
          err = true;
          break;
        }
        p += w;
        n -= static_cast<size_t>(w);
      }
      if (!err && mode_ == MS_WAL_FSYNC) err = fsync(fd) != 0;
    }
    {
      std::lock_guard<std::mutex> g(pm_);
      persisted_ = std::max(persisted_, max_rev);
      if (err) io_error_ = true;
    }
    pcv_.notify_all();
  }

  std::string dir_;
  int mode_;
  std::mutex qm_;
  std::condition_variable qcv_;
  std::deque<WalRec> q_;
  int64_t last_enqueued_ = 0;
  bool stop_ = false;
  std::mutex pm_;
  std::condition_variable pcv_;
  int64_t persisted_ = 0;
  bool io_error_ = false;
  std::mutex fd_mu_;
  std::map<std::string, int> fds_;
  std::thread writer_;
};

struct PrefixStats {
  int64_t keys = 0;
  int64_t bytes = 0;
};

// What the keys of one prefix share, resolved when a key leaves the
// previous key's prefix and not once a key.
struct PrefixRun {
  static constexpr int kUnresolved = -2;
  std::string prefix;
  bool closed = false;  // see prefix_split
  PrefixStats* stats = nullptr;
  int fd = kUnresolved;  // the prefix's WAL file, from its first durable write
};

}  // namespace

// ---- the store ------------------------------------------------------------

struct ms_store {
  mutable std::shared_mutex mu;

  // Both indexes view the bytes of their item's Key.
  std::map<std::string_view, TreeItem*> sorted;  // live keys, ordered
  std::unordered_map<std::string_view, TreeItem*> by_key;  // O(1) point lookup

  // Global revision log: entry for revision r lives at log[r - log_base].
  std::deque<RevEntry> log;
  int64_t log_base = 1;   // revision of log.front()
  int64_t current = 0;    // latest allocated revision
  int64_t compacted = 0;  // compact revision (0 = never)

  std::map<int64_t, std::shared_ptr<Watcher>> watchers;
  int64_t next_watcher = 0;
  int prev_watchers = 0;  // of them, how many asked for prev_kv

  std::map<std::string, PrefixStats> prefix_stats;
  std::atomic<int64_t> live_keys{0};
  std::atomic<int64_t> db_bytes{0};

  std::unique_ptr<Wal> wal;
  std::vector<std::string> no_write_prefixes;
  bool replaying = false;

  // ---- the frame: one write critical section (a batch, or one set).
  // Its writes' events gather here in revision order and go to the
  // watchers once, at its end (Frame, fan_out).
  std::vector<Event> frame;
  // Where the frame's previous insert into `sorted` left off: keys of a
  // frame mostly arrive in order, and the next one goes right here.
  std::map<std::string_view, TreeItem*>::iterator sorted_hint = sorted.end();
  PrefixRun run;

  // ---- contention metrics (reference metrics.rs:78-94, store.rs:478-495).
  // Store-mutex acquisitions by (method, read|write), with wait time
  // accumulated only when the acquisition actually contended — the
  // try_lock fast path keeps the uncontended cost to one relaxed add.
  enum Method {
    M_SET, M_PUT_BATCH, M_BIND_BATCH, M_RANGE, M_COMPACT, M_WATCH, M_STATS,
    M_METHODS
  };
  static constexpr const char* kMethodNames[M_METHODS] = {
      "set", "put_batch", "bind_batch", "range", "compact", "watch", "stats"};
  std::atomic<int64_t> lock_count[M_METHODS][2]{};
  std::atomic<int64_t> lock_wait_ns[M_METHODS][2]{};
  // Watcher-queue pressure.  The reference *blocks* a slow notify and
  // times it (store.rs:478-495); this design drops-at-cap instead (the
  // consumer resyncs), so the analog is enqueue/drop counts and the
  // high-water queue depth.  enqueue_batches counts the times a writer
  // took a watcher's queue: enqueued / enqueue_batches is the events
  // handed over per acquisition.
  std::atomic<int64_t> watch_enqueued{0};
  std::atomic<int64_t> watch_enqueue_batches{0};
  std::atomic<int64_t> watch_dropped_total{0};
  std::atomic<int64_t> watch_queue_hwm{0};

  ~ms_store() {
    wal.reset();  // drain writer before freeing items
    // by_key's views dangle from here on; nothing reads them again.
    for (auto& [k, item] : by_key) delete item;
  }

  bool wal_skip(std::string_view key) const {
    for (const auto& p : no_write_prefixes)
      if (key.compare(0, p.size(), p) == 0) return true;
    return false;
  }

  TreeItem* find(std::string_view key) const {
    auto it = by_key.find(key);
    return it == by_key.end() ? nullptr : it->second;
  }

  PrefixRun& run_of(std::string_view key) {
    if (!run.closed || key.compare(0, run.prefix.size(), run.prefix) != 0) {
      run.prefix = prefix_split(key, &run.closed);
      run.stats = &prefix_stats[run.prefix];
      run.fd = PrefixRun::kUnresolved;
    }
    return run;
  }

  // The write of `item` that revision rev reads (largest touch <= rev):
  // MS_OK with *out null where the key did not exist, a tombstone where
  // it was deleted; MS_ERR_COMPACTED for a rev below the compact revision
  // whose history is gone.
  int value_at(const TreeItem* item, int64_t rev, const Rec** out) const {
    auto it = std::upper_bound(item->revs.begin(), item->revs.end(), rev);
    *out = nullptr;
    if (it == item->revs.begin()) return MS_OK;
    int64_t r = *(it - 1);
    if (r == item->latest->mod_rev) {
      *out = item->latest.get();
    } else if (r >= log_base) {
      *out = log[static_cast<size_t>(r - log_base)].rec.get();
    } else if (item->base && r == item->base->mod_rev) {
      *out = item->base.get();
    } else if (rev < compacted) {
      return MS_ERR_COMPACTED;
    }
    // else: the touch live at the compact revision left no base, so it
    // was a tombstone (compaction keeps a live value): the key was absent.
    return MS_OK;
  }

  // Hand the frame's events to every watcher they match, each watcher's
  // run under one acquisition of its queue: called at the end of the
  // write critical section, inside it, so a watcher's queue is in
  // revision order by construction and holds a write before the call
  // that made it returns.  `exclude` is ms_bind_batch's exclude_watcher.
  // The cap falls event by event: a queue takes the first events of its
  // run that it has room for and counts the rest as dropped.
  void fan_out(int64_t exclude) {
    if (frame.empty()) return;
    for (auto& [id, w] : watchers) {
      if (id == exclude) continue;
      std::unique_lock<std::mutex> g(w->m, std::defer_lock);
      size_t room = 0;
      int64_t taken = 0, lost = 0;
      for (const Event& ev : frame) {
        if (ev.rec->mod_rev < w->min_rev || !w->matches(ev.rec->key->view()))
          continue;
        if (!g.owns_lock()) {
          g.lock();
          if (w->canceled) break;
          room = w->queue_cap > w->q.size() ? w->queue_cap - w->q.size() : 0;
        }
        if (room == 0) {
          lost++;
          continue;
        }
        room--;
        taken++;
        w->q.push_back(w->want_prev ? ev : Event{ev.rec, RecRef()});
      }
      if (!g.owns_lock() || w->canceled) continue;
      watch_enqueue_batches.fetch_add(1, std::memory_order_relaxed);
      if (lost) {
        w->dropped += lost;
        watch_dropped_total.fetch_add(lost, std::memory_order_relaxed);
      }
      if (taken == 0) continue;
      watch_enqueued.fetch_add(taken, std::memory_order_relaxed);
      const int64_t depth = static_cast<int64_t>(w->q.size());
      int64_t hwm = watch_queue_hwm.load(std::memory_order_relaxed);
      while (depth > hwm &&
             !watch_queue_hwm.compare_exchange_weak(
                 hwm, depth, std::memory_order_relaxed)) {
      }
      w->cv.notify_one();
    }
    frame.clear();
  }
};

namespace {

// Scoped store-mutex guards that feed the contention metrics.
struct WGuard {
  std::unique_lock<std::shared_mutex> g;
  WGuard(ms_store* s, int m) : g(s->mu, std::defer_lock) {
    if (!g.try_lock()) {
      int64_t t0 = now_ns();
      g.lock();
      s->lock_wait_ns[m][1].fetch_add(now_ns() - t0,
                                      std::memory_order_relaxed);
    }
    s->lock_count[m][1].fetch_add(1, std::memory_order_relaxed);
  }
};

struct RGuard {
  std::shared_lock<std::shared_mutex> g;
  RGuard(ms_store* s, int m) : g(s->mu, std::defer_lock) {
    if (!g.try_lock()) {
      int64_t t0 = now_ns();
      g.lock();
      s->lock_wait_ns[m][0].fetch_add(now_ns() - t0,
                                      std::memory_order_relaxed);
    }
    s->lock_count[m][0].fetch_add(1, std::memory_order_relaxed);
  }
};

// One frame: the store's write lock and, at the end and still inside it,
// the fan-out of what the frame wrote.
struct Frame {
  ms_store* s;
  int64_t exclude;
  WGuard g;
  Frame(ms_store* s, int m, int64_t exclude_watcher = -1)
      : s(s), exclude(exclude_watcher), g(s, m) {
    s->sorted_hint = s->sorted.end();
  }
  ~Frame() { s->fan_out(exclude); }
};

}  // namespace

// ---- open / replay --------------------------------------------------------

static int64_t store_set_locked(ms_store* s, std::string_view key,
                                const uint8_t* val, size_t vlen, bool is_del,
                                int has_req, int req_is_version,
                                int64_t req_val, int64_t lease,
                                int64_t* latest_rev_out, uint8_t** cur_out,
                                size_t* cur_len_out, bool* fsync_wait_out);

ms_store* ms_open(const char* wal_dir, int wal_mode,
                  const char* no_write_prefixes) {
  auto* s = new ms_store();
  if (no_write_prefixes && *no_write_prefixes) {
    std::string all(no_write_prefixes);
    size_t pos = 0;
    while (pos <= all.size()) {
      size_t nl = all.find('\n', pos);
      if (nl == std::string::npos) nl = all.size();
      if (nl > pos) s->no_write_prefixes.push_back(all.substr(pos, nl - pos));
      pos = nl + 1;
    }
  }

  // Revisions start at 1 like etcd: write a dummy key before the WAL is
  // attached so it is never persisted (reference main.rs:103-104).
  store_set_locked(s, "~", reinterpret_cast<const uint8_t*>(""), 0, false, 0,
                   0, 0, 0, nullptr, nullptr, nullptr, nullptr);

  std::string dir = wal_dir ? wal_dir : "";
  if (!dir.empty()) {
    mkdir(dir.c_str(), 0755);
    // Replay existing files before attaching the writer.
    struct Replayed {
      int64_t rev;
      std::string key, val;
      bool is_del;
    };
    std::vector<std::vector<Replayed>> files;
    {
      // enumerate prefix_*.wal
      DIR* d = opendir(dir.c_str());
      if (d) {
        struct dirent* de;
        while ((de = readdir(d)) != nullptr) {
          std::string name = de->d_name;
          if (name.rfind("prefix_", 0) != 0) continue;
          if (name.size() < 4 || name.substr(name.size() - 4) != ".wal")
            continue;
          FILE* f = fopen((dir + "/" + name).c_str(), "rb");
          if (!f) continue;
          std::vector<Replayed> recs;
          for (;;) {
            uint64_t r;
            uint32_t kl, vl;
            if (fread(&r, 8, 1, f) != 1) break;
            if (fread(&kl, 4, 1, f) != 1) break;
            if (fread(&vl, 4, 1, f) != 1) break;
            Replayed rec;
            rec.rev = static_cast<int64_t>(r);
            rec.key.resize(kl);
            if (kl && fread(rec.key.data(), 1, kl, f) != kl) break;
            rec.is_del = (vl == kDeleteMarker);
            if (!rec.is_del) {
              rec.val.resize(vl);
              if (vl && fread(rec.val.data(), 1, vl, f) != vl) break;
            }
            recs.push_back(std::move(rec));
          }
          fclose(f);
          if (!recs.empty()) files.push_back(std::move(recs));
        }
        closedir(d);
      }
    }
    // k-way merge by recorded revision.
    using HeapItem = std::pair<int64_t, std::pair<size_t, size_t>>;
    std::priority_queue<HeapItem, std::vector<HeapItem>, std::greater<>> heap;
    for (size_t i = 0; i < files.size(); i++)
      heap.push({files[i][0].rev, {i, 0}});
    s->replaying = true;
    while (!heap.empty()) {
      auto [rev, fi] = heap.top();
      heap.pop();
      auto& rec = files[fi.first][fi.second];
      store_set_locked(s, rec.key,
                       reinterpret_cast<const uint8_t*>(rec.val.data()),
                       rec.val.size(), rec.is_del, 0, 0, 0, 0, nullptr,
                       nullptr, nullptr, nullptr);
      if (fi.second + 1 < files[fi.first].size())
        heap.push({files[fi.first][fi.second + 1].rev,
                   {fi.first, fi.second + 1}});
    }
    s->replaying = false;
    s->wal = std::make_unique<Wal>(dir, wal_mode);
  }
  return s;
}

void ms_close(ms_store* s) { delete s; }
void ms_free(void* p) { free(p); }

// ---- set ------------------------------------------------------------------

// Commit `rec` as the next write of `item`, under the write lock: the
// revision, the stats, the ordered index, the MVCC log, the WAL queue and
// the frame's events.  The caller has decided that the write happens (CAS
// checked, a delete only of a present key) and has filled the value.
static int64_t commit_locked(ms_store* s, TreeItem* item, RecRef rec,
                             bool* fsync_wait_out) {
  const std::string_view key = item->key->view();
  const Rec* old = item->latest.get();
  const bool present = old && !old->del;
  const int64_t old_bytes =
      present ? static_cast<int64_t>(key.size() + old->vlen) : 0;
  const int64_t rev = ++s->current;
  PrefixRun& run = s->run_of(key);

  rec->mod_rev = rev;
  int64_t new_bytes = 0;
  if (rec->del) {
    run.stats->keys--;
    s->live_keys.fetch_sub(1, std::memory_order_relaxed);
    // latest index holds live keys only
    auto it = s->sorted.find(key);
    if (it == s->sorted_hint) ++s->sorted_hint;
    s->sorted.erase(it);
  } else {
    if (!present) {
      rec->create_rev = rev;
      rec->version = 1;
      run.stats->keys++;
      s->live_keys.fetch_add(1, std::memory_order_relaxed);
      // a new key, or a tombstone resurrected into the index
      s->sorted_hint =
          std::next(s->sorted.emplace_hint(s->sorted_hint, key, item));
    } else {
      rec->create_rev = old->create_rev;
      rec->version = old->version + 1;
    }
    new_bytes = static_cast<int64_t>(key.size() + rec->vlen);
  }
  run.stats->bytes += new_bytes - old_bytes;
  s->db_bytes.fetch_add(new_bytes - old_bytes, std::memory_order_relaxed);

  RecRef prev;
  if (present && s->prev_watchers) prev = std::move(item->latest);
  item->latest = rec;
  item->revs.push_back(rev);
  s->log.push_back(RevEntry{item, rec});

  // WAL append (inside the lock: queue order == revision order).
  if (s->wal && !s->replaying && !s->wal_skip(key)) {
    if (run.fd == PrefixRun::kUnresolved) run.fd = s->wal->FdFor(run.prefix);
    s->wal->Append(run.fd, rec);
    if (fsync_wait_out) *fsync_wait_out = s->wal->fsync_mode();
  }

  if (!s->watchers.empty())
    s->frame.push_back(Event{std::move(rec), std::move(prev)});
  return rev;
}

static int64_t store_set_locked(ms_store* s, std::string_view key,
                                const uint8_t* val, size_t vlen, bool is_del,
                                int has_req, int req_is_version,
                                int64_t req_val, int64_t lease,
                                int64_t* latest_rev_out, uint8_t** cur_out,
                                size_t* cur_len_out, bool* fsync_wait_out) {
  TreeItem* item = s->find(key);
  const bool present = item && item->present();

  if (has_req) {
    int64_t have = !present ? 0
                   : req_is_version ? item->latest->version
                                    : item->latest->mod_rev;
    if (have != req_val) {
      if (latest_rev_out) *latest_rev_out = s->current;
      if (cur_out && present) {
        std::string b;
        put_kv(b, *item->latest);
        *cur_out = to_malloc(b, cur_len_out);
      }
      return MS_ERR_CAS;
    }
  }

  if (is_del && !present) return 0;  // delete of absent key: no revision

  if (!item) {
    item = new TreeItem();
    item->key = Key::make(key);
    s->by_key.emplace(item->key->view(), item);
  }
  RecRef rec = Rec::make(item->key, is_del ? 0 : vlen, is_del);
  if (!is_del) {
    memcpy(rec->val, val, vlen);
    rec->lease = lease;
  }
  return commit_locked(s, item, std::move(rec), fsync_wait_out);
}

static int64_t ms_set_impl(ms_store* s, const uint8_t* key, size_t klen,
                           const uint8_t* val, size_t vlen, int has_req,
                           int req_is_version, int64_t req_val, int64_t lease,
                           int64_t* latest_rev_out, uint8_t** cur_out,
                           size_t* cur_len_out, bool wait_durable) {
  std::string_view k(reinterpret_cast<const char*>(key), klen);
  int64_t rev;
  bool fsync_wait = false;
  {
    Frame f(s, ms_store::M_SET);  // a frame of one
    rev = store_set_locked(s, k, val, vlen, val == nullptr, has_req,
                           req_is_version, req_val, lease, latest_rev_out,
                           cur_out, cur_len_out, &fsync_wait);
  }
  if (wait_durable && rev > 0 && fsync_wait) {
    // fsync mode: block until durable (reference store.rs:415-437).
    s->wal->WaitPersisted(rev);
  }
  return rev;
}

int64_t ms_set(ms_store* s, const uint8_t* key, size_t klen,
               const uint8_t* val, size_t vlen, int has_req,
               int req_is_version, int64_t req_val, int64_t lease,
               int64_t* latest_rev_out, uint8_t** cur_out,
               size_t* cur_len_out) {
  return ms_set_impl(s, key, klen, val, vlen, has_req, req_is_version,
                     req_val, lease, latest_rev_out, cur_out, cur_len_out,
                     true);
}

int64_t ms_set_nowait(ms_store* s, const uint8_t* key, size_t klen,
                      const uint8_t* val, size_t vlen, int has_req,
                      int req_is_version, int64_t req_val, int64_t lease,
                      int64_t* latest_rev_out, uint8_t** cur_out,
                      size_t* cur_len_out) {
  return ms_set_impl(s, key, klen, val, vlen, has_req, req_is_version,
                     req_val, lease, latest_rev_out, cur_out, cur_len_out,
                     false);
}

int ms_wal_mode(ms_store* s) {
  return s->wal ? s->wal->mode() : MS_WAL_NONE;
}

int64_t ms_wal_persisted_revision(ms_store* s) {
  return s->wal ? s->wal->persisted_revision() : 0;
}

int ms_wal_io_error(ms_store* s) {
  return s->wal && s->wal->io_error() ? 1 : 0;
}

int64_t ms_put_batch(ms_store* s, const uint8_t* buf, size_t len, int n,
                     int64_t lease) {
  if (n < 0) return MS_ERR_INVALID;
  // Validate the WHOLE frame before applying anything (and before taking
  // the lock): frames arrive from the wire, and a malformed one must
  // reject atomically — not after a prefix of the wave has committed,
  // which would make the INVALID_ARGUMENT response a lie and skip the
  // fsync wait for the records already applied.
  {
    size_t off = 0;
    for (int i = 0; i < n; i++) {
      if (off + 8 > len) return MS_ERR_INVALID;
      uint32_t klen, vlen;
      memcpy(&klen, buf + off, 4);
      memcpy(&vlen, buf + off + 4, 4);
      off += 8;
      const size_t vbytes = vlen == kDeleteMarker ? 0 : vlen;
      if (off + klen + vbytes > len) return MS_ERR_INVALID;
      off += klen + vbytes;
    }
  }
  int64_t last = 0;
  bool fsync_wait = false;
  {
    Frame f(s, ms_store::M_PUT_BATCH);
    size_t off = 0;
    for (int i = 0; i < n; i++) {
      uint32_t klen, vlen;
      memcpy(&klen, buf + off, 4);
      memcpy(&vlen, buf + off + 4, 4);
      off += 8;
      const bool is_del = vlen == kDeleteMarker;
      const size_t vbytes = is_del ? 0 : vlen;
      std::string_view key(reinterpret_cast<const char*>(buf + off), klen);
      off += klen;
      bool fw = false;
      int64_t rev =
          store_set_locked(s, key, is_del ? nullptr : buf + off, vbytes,
                           is_del, 0, 0, 0, lease, nullptr, nullptr, nullptr,
                           &fw);
      off += vbytes;
      if (rev > 0) last = rev;
      fsync_wait |= fw;
    }
    if (last == 0) last = s->current;
  }
  if (fsync_wait) s->wal->WaitPersisted(last);
  return last;
}

namespace {

// Structural splice contract shared with the Python bind fast path
// (k8s1m_tpu/control/coordinator.py splice_node_name): encode_pod always
// opens spec with schedulerName, and this pattern cannot occur inside a
// JSON string literal (the quotes would be escaped).
constexpr char kSpecMark[] = "\"spec\":{\"schedulerName\":";
constexpr size_t kSpecCut = 8;  // len("\"spec\":{")
constexpr char kNodeNameKey[] = "\"nodeName\"";
constexpr char kNodeNameOpen[] = "\"nodeName\":\"";
constexpr char kNodeNameClose[] = "\",";

// memmem, not string::find: find looks for the pattern's first byte and
// compares at every hit, and a pattern that opens with a quote hits at
// every quote of a JSON value.
inline const char* find_lit(std::string_view v, const char* lit,
                            size_t lit_len) {
  return static_cast<const char*>(memmem(v.data(), v.size(), lit, lit_len));
}
// A literal and its length, for the splice here and the pod parser below.
#define LIT(name) name, sizeof(name) - 1

bool json_plain(const uint8_t* p, size_t n) {
  for (size_t i = 0; i < n; i++)
    if (p[i] == '"' || p[i] == '\\' || p[i] < 0x20) return false;
  return true;
}

}  // namespace

int ms_bind_batch(ms_store* s, const uint8_t* buf, size_t len, int n,
                  int64_t exclude_watcher, int64_t** out) {
  if (n < 0) return MS_ERR_INVALID;
  // Pre-validate the whole frame (see ms_put_batch): reject atomically
  // before any bind commits.
  {
    size_t off = 0;
    for (int i = 0; i < n; i++) {
      if (off + 16 > len) return MS_ERR_INVALID;
      uint32_t klen, nlen;
      memcpy(&klen, buf + off + 8, 4);
      memcpy(&nlen, buf + off + 12, 4);
      off += 16;
      if (off + klen + nlen > len) return MS_ERR_INVALID;
      off += klen + nlen;
    }
  }
  auto* results = static_cast<int64_t*>(malloc(sizeof(int64_t) * (n ? n : 1)));
  int bound = 0;
  int64_t last = 0;
  bool fsync_wait = false;
  {
    Frame f(s, ms_store::M_BIND_BATCH, exclude_watcher);
    size_t off = 0;
    for (int i = 0; i < n; i++) {
      int64_t req_mod;
      uint32_t klen, nlen;
      memcpy(&req_mod, buf + off, 8);
      memcpy(&klen, buf + off + 8, 4);
      memcpy(&nlen, buf + off + 12, 4);
      off += 16;
      std::string_view key(reinterpret_cast<const char*>(buf + off), klen);
      off += klen;
      const uint8_t* name = buf + off;
      off += nlen;

      // The one look-up of the record: the item found here is the item
      // the bind is committed to.
      TreeItem* item = s->find(key);
      if (!item || !item->present() || item->latest->mod_rev != req_mod) {
        results[i] = MS_ERR_CAS;
        continue;
      }
      const Rec& cur = *item->latest;
      const std::string_view val = cur.value();
      const char* mark = find_lit(val, LIT(kSpecMark));
      if (mark == nullptr || find_lit(val, LIT(kNodeNameKey)) != nullptr ||
          !json_plain(name, nlen)) {
        results[i] = MS_ERR_INVALID;
        continue;
      }
      // The spliced value is written once, into the record that becomes
      // the key's latest.
      const size_t cut = static_cast<size_t>(mark - val.data()) + kSpecCut;
      RecRef rec = Rec::make(item->key, val.size() + nlen +
                                            sizeof(kNodeNameOpen) - 1 +
                                            sizeof(kNodeNameClose) - 1);
      rec->lease = cur.lease;
      char* p = rec->val;
      auto put = [&p](const void* src, size_t n) {
        memcpy(p, src, n);
        p += n;
      };
      put(val.data(), cut);
      put(LIT(kNodeNameOpen));
      put(name, nlen);
      put(LIT(kNodeNameClose));
      put(val.data() + cut, val.size() - cut);

      bool fw = false;
      const int64_t rev = commit_locked(s, item, std::move(rec), &fw);
      results[i] = rev;
      bound++;
      last = rev;
      fsync_wait |= fw;
    }
  }
  if (fsync_wait && last > 0) s->wal->WaitPersisted(last);
  *out = results;
  return bound;
}

// ---- range ----------------------------------------------------------------

namespace {

// end conventions: len 0 => single key; "\0" => infinity; else exclusive.
enum class RangeKind { kSingle, kToInfinity, kBounded };

RangeKind range_kind(const uint8_t* end, size_t end_len) {
  if (end == nullptr || end_len == 0) return RangeKind::kSingle;
  if (end_len == 1 && end[0] == 0) return RangeKind::kToInfinity;
  return RangeKind::kBounded;
}

}  // namespace

int ms_range(ms_store* s, const uint8_t* start, size_t start_len,
             const uint8_t* end, size_t end_len, int64_t rev, int64_t limit,
             int count_only, int keys_only, uint8_t** out, size_t* out_len) {
  const std::string_view k(reinterpret_cast<const char*>(start), start_len);
  RangeKind kind = range_kind(end, end_len);
  const std::string_view e =
      kind == RangeKind::kBounded
          ? std::string_view(reinterpret_cast<const char*>(end), end_len)
          : std::string_view();

  RGuard g(s, ms_store::M_RANGE);
  if (rev > 0) {
    if (rev > s->current) return MS_ERR_FUTURE_REV;
    if (s->compacted && rev < s->compacted) return MS_ERR_COMPACTED;
  }
  const bool historical = rev > 0 && rev < s->current;

  std::string body;
  int64_t total = 0;
  uint32_t n = 0;

  auto emit = [&](const Rec& r) {
    total++;
    if (count_only) return;
    if (limit > 0 && n >= limit) return;
    put_kv(body, r, keys_only != 0);
    n++;
  };
  // The write of `item` the range reads, if it holds a value there.
  auto emit_at = [&](const TreeItem* item) {
    const Rec* r = item->latest.get();
    if (historical) {
      int rc = s->value_at(item, rev, &r);
      if (rc != MS_OK) return rc;
    }
    if (r && !r->del) emit(*r);
    return static_cast<int>(MS_OK);
  };

  if (kind == RangeKind::kSingle) {
    // by_key holds tombstoned keys too, which a historical read may need.
    if (const TreeItem* item = s->find(k)) {
      int rc = emit_at(item);
      if (rc != MS_OK) return rc;
    }
  } else if (historical) {
    // Historical ranges must see keys that are tombstoned *now* but were
    // live at `rev`; those are absent from `sorted`.  Iterate an ordered
    // snapshot of all item keys in range: item count == live + tombstoned
    // keys, and tombstones are GC'd at compaction, keeping this bounded.
    std::vector<const TreeItem*> in_range;
    for (auto& [key, item] : s->by_key) {
      if (key < k) continue;
      if (kind == RangeKind::kBounded && key >= e) continue;
      in_range.push_back(item);
    }
    std::sort(in_range.begin(), in_range.end(), [](auto* a, auto* b) {
      return a->key->view() < b->key->view();
    });
    for (const TreeItem* item : in_range) {
      int rc = emit_at(item);
      if (rc != MS_OK) return rc;
    }
  } else {
    for (auto it = s->sorted.lower_bound(k); it != s->sorted.end(); ++it) {
      if (kind == RangeKind::kBounded && it->first >= e) break;
      emit(*it->second->latest);
      // Approximate count beyond the limit (the reference allows this,
      // README.adoc:326-328): one element past the limit proves
      // more=1, then stop — a paginated list over 1M keys must cost
      // O(limit), not O(keys).
      if (limit > 0 && total > limit) break;
    }
  }

  std::string head;
  put_i64(head, s->current);
  put_i64(head, total);
  put_u32(head, n);
  put_u8(head, (limit > 0 && total > n) ? 1 : 0);
  head.append(body);
  *out = to_malloc(head, out_len);
  return MS_OK;
}

int64_t ms_current_revision(ms_store* s) {
  std::shared_lock<std::shared_mutex> g(s->mu);
  return s->current;
}

int64_t ms_compact_revision(ms_store* s) {
  std::shared_lock<std::shared_mutex> g(s->mu);
  return s->compacted;
}

int64_t ms_progress_revision(ms_store* s) { return ms_current_revision(s); }

// ---- compaction -----------------------------------------------------------

int ms_compact(ms_store* s, int64_t rev) {
  WGuard g(s, ms_store::M_COMPACT);
  if (rev <= s->compacted) return MS_ERR_COMPACTED;
  if (rev > s->current) return MS_ERR_FUTURE_REV;
  s->compacted = rev;
  while (s->log_base < rev && !s->log.empty()) {
    RevEntry& e = s->log.front();
    TreeItem* item = e.item;
    const int64_t r = s->log_base;
    if (item) {
      // Preserve the value live at the compact revision (etcd keeps
      // non-superseded versions; see header).
      auto it = std::upper_bound(item->revs.begin(), item->revs.end(), rev);
      int64_t live = (it == item->revs.begin()) ? 0 : *(it - 1);
      if (r == live && !e.rec->del) {
        // Keep it even when r == mod_rev today: a later write would move
        // `latest` on and strand reads in [compact_rev, that write).
        item->base = e.rec;
      }
      // Tombstone GC (the reference's TODO, store.rs:832): a key deleted
      // before the compact revision with no later writes can be dropped
      // entirely.
      if (e.rec->del && e.rec.get() == item->latest.get()) {
        // No log reference remains (this was the item's last touch); the
        // key's bytes live on for as long as a queued event holds them.
        s->by_key.erase(item->key->view());
        delete item;
      }
    }
    s->log.pop_front();
    s->log_base++;
  }
  return MS_OK;
}

// ---- watches --------------------------------------------------------------

int64_t ms_watch_create(ms_store* s, const uint8_t* start, size_t start_len,
                        const uint8_t* end, size_t end_len, int64_t start_rev,
                        int want_prev_kv, int64_t queue_cap,
                        int64_t* compact_rev_out) {
  WGuard g(s, ms_store::M_WATCH);
  if (start_rev > 0 && s->compacted && start_rev < s->compacted) {
    if (compact_rev_out) *compact_rev_out = s->compacted;
    return MS_ERR_COMPACTED;
  }
  auto w = std::make_shared<Watcher>();
  w->id = s->next_watcher++;
  // 0 = default cap.  Tick-driven consumers (the coordinator's pod
  // firehose) pass a deep cap: they drain per cycle, not continuously,
  // so a 10K cap would overflow between cycles under bursty churn.
  if (queue_cap > 0) w->queue_cap = static_cast<size_t>(queue_cap);
  w->start.assign(reinterpret_cast<const char*>(start), start_len);
  RangeKind kind = range_kind(end, end_len);
  w->single = kind == RangeKind::kSingle;
  w->to_infinity = kind == RangeKind::kToInfinity;
  if (kind == RangeKind::kBounded)
    w->end.assign(reinterpret_cast<const char*>(end), end_len);
  w->want_prev = want_prev_kv != 0;
  w->min_rev = start_rev;

  // Replay past changes >= start_rev from the revision log, in revision
  // order (reference store.rs:766-806 walks per-key revision lists; the
  // log scan is equivalent and already ordered).
  if (start_rev > 0 && start_rev <= s->current) {
    for (int64_t r = std::max(start_rev, s->log_base); r <= s->current; r++) {
      const RevEntry& e = s->log[static_cast<size_t>(r - s->log_base)];
      if (!w->matches(e.rec->key->view())) continue;
      Event ev{e.rec, RecRef()};
      if (w->want_prev) {
        // prev = value just before r, even across the start revision
        // (reference watch_service_test.rs:372-425 pins this).
        const Rec* prev;
        if (s->value_at(e.item, r - 1, &prev) == MS_OK && prev && !prev->del)
          ev.prev = RecRef::share(const_cast<Rec*>(prev));
      }
      w->q.push_back(std::move(ev));
    }
  }

  if (w->want_prev) s->prev_watchers++;
  s->watchers.emplace(w->id, w);
  return w->id;
}

int ms_watch_cancel(ms_store* s, int64_t watcher_id) {
  std::shared_ptr<Watcher> w;
  {
    WGuard g(s, ms_store::M_WATCH);
    auto it = s->watchers.find(watcher_id);
    if (it == s->watchers.end()) return MS_ERR_NOT_FOUND;
    w = it->second;
    s->watchers.erase(it);
    if (w->want_prev) s->prev_watchers--;
  }
  {
    std::lock_guard<std::mutex> g(w->m);
    w->canceled = true;
  }
  w->cv.notify_all();
  return MS_OK;
}

int ms_watch_poll(ms_store* s, int64_t watcher_id, int max_events,
                  int timeout_ms, uint8_t** out, size_t* out_len) {
  std::shared_ptr<Watcher> w;
  {
    RGuard g(s, ms_store::M_WATCH);
    auto it = s->watchers.find(watcher_id);
    if (it != s->watchers.end()) w = it->second;
  }
  if (!w) return MS_ERR_NOT_FOUND;

  bool canceled;
  const std::deque<Event> events = w->take(max_events, timeout_ms, &canceled);

  std::string b;
  put_u32(b, static_cast<uint32_t>(events.size()));
  put_u8(b, canceled ? 1 : 0);
  for (auto& ev : events) {
    put_u8(b, ev.rec->del ? 1 : 0);
    put_u8(b, ev.prev ? 1 : 0);
    put_kv(b, *ev.rec);
    if (ev.prev) put_kv(b, *ev.prev);
  }
  *out = to_malloc(b, out_len);
  return static_cast<int>(events.size());
}

namespace {

// ---- canonical pod fast parser -------------------------------------------
// The exact byte landmarks of this framework's encode_pod for pods whose
// only free parts are a flat label map, a nodeSelector, a toleration list,
// an affinity object and a list of topology spread constraints, in
// encode_pod's order (k8s1m_tpu/control/objects.py decode_pod_fast is the
// Python twin; the two parsers accept the same inputs so the fast lane and
// the fallback path can never disagree).  None of the five is interpreted
// here: the parser proves the grammar of the label map, the nodeSelector
// (a flat map of strings too) and the tolerations, and that affinity is a
// balanced object and the spread constraints a balanced array, and hands
// back their byte spans, which the frame carries once per distinct
// quintuple (a "shape").  Anything else — priority, escapes, members out
// of order — is left for the caller's full JSON parser.
constexpr char kPodHead[] =
    "{\"apiVersion\":\"v1\",\"kind\":\"Pod\",\"metadata\":{\"name\":\"";
constexpr char kPodNs[] = "\",\"namespace\":\"";
constexpr char kPodLabels[] = "\",\"labels\":{";
constexpr char kPodSpec[] = "},\"spec\":{";
constexpr char kPodNode[] = "\"nodeName\":\"";
constexpr char kPodSched[] = "\"schedulerName\":\"";
constexpr char kPodContainers[] =
    "\",\"containers\":[{\"name\":\"app\",\"image\":\"img\","
    "\"resources\":{\"requests\":{\"cpu\":\"";
constexpr char kPodMem[] = "\",\"memory\":\"";
constexpr char kPodCtrEnd[] = "\"}}}]";
// encode_pod appends nodeName after containers (dict insertion order); the
// bind splice inserts it before schedulerName.  Both are accepted.
constexpr char kPodNodeApp[] = ",\"nodeName\":\"";
constexpr char kPodSelector[] = ",\"nodeSelector\":{";
constexpr char kPodTols[] = ",\"tolerations\":[";
constexpr char kPodAffinity[] = ",\"affinity\":{";
constexpr char kPodSpread[] = ",\"topologySpreadConstraints\":[";
constexpr char kPodEnd[] = "},\"status\":{\"phase\":\"Pending\"}}";
constexpr char kTolKey[] = "\"key\":\"";
constexpr char kTolOp[] = "\"operator\":\"";
constexpr char kTolValue[] = ",\"value\":\"";
constexpr char kTolEffect[] = ",\"effect\":\"";

constexpr int kShapeSpans = 5;
enum { kLabels, kSelector, kTols, kAffinity, kSpread };

struct PodParse {
  bool has_node = false;
  bool sched_match = false;
  int32_t cpu = 0, mem = 0;
  const char* node = nullptr;
  size_t node_len = 0;
  // The shape, in encode_pod's order: the contents of the braces of the
  // label map and of nodeSelector, of the brackets of the toleration list,
  // of the braces of affinity and of the brackets of
  // topologySpreadConstraints (all empty for the bare pod).
  const char* span[kShapeSpans] = {"", "", "", "", ""};
  size_t len[kShapeSpans] = {0, 0, 0, 0, 0};
};

inline bool lit_at(std::string_view v, size_t pos, const char* lit,
                   size_t lit_len) {
  return pos + lit_len <= v.size() && memcmp(v.data() + pos, lit, lit_len) == 0;
}

// Parse an int span with a required suffix; false on overflow/non-digit.
bool parse_qty(const char* p, size_t n, const char* suffix, size_t suffix_len,
               int32_t* out) {
  if (n <= suffix_len || memcmp(p + n - suffix_len, suffix, suffix_len) != 0)
    return false;
  n -= suffix_len;
  if (n == 0 || n > 9) return false;
  int32_t acc = 0;
  for (size_t i = 0; i < n; i++) {
    if (p[i] < '0' || p[i] > '9') return false;
    acc = acc * 10 + (p[i] - '0');
  }
  *out = acc;
  return true;
}

// A flat {"k":"v",...} map of plain strings, from just past its opening
// brace; *i ends just past the closing brace (objects.py _scan_labels).
// The value holds no backslash, so a string ends at the next quote.
bool scan_labels(std::string_view v, size_t* i) {
  size_t p = *i;
  if (p < v.size() && v[p] == '}') {
    *i = p + 1;
    return true;
  }
  for (;;) {
    if (p >= v.size() || v[p] != '"') return false;
    size_t j = v.find('"', p + 1);
    if (j == std::string::npos || !lit_at(v, j, "\":\"", 3)) return false;
    j = v.find('"', j + 3);
    if (j == std::string::npos || j + 1 >= v.size()) return false;
    p = j + 2;
    if (v[j + 1] == ',') continue;
    if (v[j + 1] != '}') return false;
    *i = p;
    return true;
  }
}

// One or more toleration objects exactly as encode_pod writes them
// (optional key, operator Exists|Equal, optional value, optional effect,
// in that order), from just past the opening bracket; *i ends just past
// the closing one.
bool scan_tolerations(std::string_view v, size_t* i) {
  size_t p = *i;
  for (;;) {
    if (p >= v.size() || v[p] != '{') return false;
    p++;
    if (lit_at(v, p, LIT(kTolKey))) {
      size_t j = v.find('"', p + sizeof(kTolKey) - 1);
      if (j == std::string::npos || !lit_at(v, j, "\",", 2)) return false;
      p = j + 2;
    }
    if (!lit_at(v, p, LIT(kTolOp))) return false;
    p += sizeof(kTolOp) - 1;
    if (lit_at(v, p, "Exists\"", 7)) p += 7;
    else if (lit_at(v, p, "Equal\"", 6)) p += 6;
    else return false;
    if (lit_at(v, p, LIT(kTolValue))) {
      size_t j = v.find('"', p + sizeof(kTolValue) - 1);
      if (j == std::string::npos) return false;
      p = j + 1;
    }
    if (lit_at(v, p, LIT(kTolEffect))) {
      p += sizeof(kTolEffect) - 1;
      if (lit_at(v, p, "NoSchedule\"", 11)) p += 11;
      else if (lit_at(v, p, "PreferNoSchedule\"", 17)) p += 17;
      else if (lit_at(v, p, "NoExecute\"", 10)) p += 10;
      else return false;
    }
    if (p + 1 >= v.size() || v[p] != '}') return false;
    p += 2;
    if (v[p - 1] == ',') continue;
    if (v[p - 1] != ']') return false;
    *i = p;
    return true;
  }
}

// Any JSON array (closer ']') or object (closer '}'), from just past its
// opening bracket or brace; *i ends just past the one that closes it
// (objects.py _scan_nested).  Only the nesting is proven — a string ends at
// its next quote, and brackets and braces inside one do not count; the
// consumer's JSON parser decides the rest, once per distinct span.
bool scan_nested(std::string_view v, size_t* i, char closer) {
  int depth = 1;
  for (size_t p = *i; p < v.size(); p++) {
    char c = v[p];
    if (c == '"') {
      p = v.find('"', p + 1);
      if (p == std::string::npos) return false;
    } else if (c == '[' || c == '{') {
      depth++;
    } else if ((c == ']' || c == '}') && --depth == 0) {
      if (c != closer) return false;
      *i = p + 1;
      return true;
    }
  }
  return false;
}

bool parse_pod(std::string_view v, const uint8_t* sched, size_t sched_len,
               PodParse* out) {
  if (!lit_at(v, 0, LIT(kPodHead))) return false;
  if (memchr(v.data(), '\\', v.size()) != nullptr) return false;
  size_t i = sizeof(kPodHead) - 1;
  size_t j = v.find('"', i);
  if (j == std::string::npos || !lit_at(v, j, LIT(kPodNs))) return false;
  i = j + sizeof(kPodNs) - 1;
  j = v.find('"', i);
  if (j == std::string::npos || !lit_at(v, j, LIT(kPodLabels))) return false;
  i = j + sizeof(kPodLabels) - 1;
  // One optional member: its span runs from `i` to just short of the
  // closer the scanner consumed.
  auto take = [&](int which, auto scan) {
    out->span[which] = v.data() + i;
    if (!scan()) return false;
    out->len[which] = static_cast<size_t>(v.data() + i - 1 - out->span[which]);
    return true;
  };
  if (!take(kLabels, [&] { return scan_labels(v, &i); })) return false;
  // scan_labels consumed the map's own brace; kPodSpec opens with
  // metadata's.
  if (!lit_at(v, i, LIT(kPodSpec))) return false;
  i += sizeof(kPodSpec) - 1;
  if (lit_at(v, i, LIT(kPodNode))) {
    i += sizeof(kPodNode) - 1;
    j = v.find('"', i);
    if (j == std::string::npos || !lit_at(v, j, "\",", 2)) return false;
    out->has_node = true;
    out->node = v.data() + i;
    out->node_len = j - i;
    i = j + 2;
  }
  if (!lit_at(v, i, LIT(kPodSched))) return false;
  i += sizeof(kPodSched) - 1;
  j = v.find('"', i);
  if (j == std::string::npos) return false;
  out->sched_match =
      (j - i) == sched_len && memcmp(v.data() + i, sched, sched_len) == 0;
  if (!lit_at(v, j, LIT(kPodContainers))) return false;
  i = j + sizeof(kPodContainers) - 1;
  j = v.find('"', i);
  if (j == std::string::npos || !parse_qty(v.data() + i, j - i, "m", 1, &out->cpu))
    return false;
  if (!lit_at(v, j, LIT(kPodMem))) return false;
  i = j + sizeof(kPodMem) - 1;
  j = v.find('"', i);
  if (j == std::string::npos || !parse_qty(v.data() + i, j - i, "Ki", 2, &out->mem))
    return false;
  if (!lit_at(v, j, LIT(kPodCtrEnd))) return false;
  i = j + sizeof(kPodCtrEnd) - 1;
  if (lit_at(v, i, LIT(kPodNodeApp))) {
    if (out->has_node) return false;
    i += sizeof(kPodNodeApp) - 1;
    j = v.find('"', i);
    if (j == std::string::npos) return false;
    out->has_node = true;
    out->node = v.data() + i;
    out->node_len = j - i;
    i = j + 1;
  }
  if (lit_at(v, i, LIT(kPodSelector))) {
    i += sizeof(kPodSelector) - 1;
    if (!take(kSelector, [&] { return scan_labels(v, &i); })) return false;
  }
  if (lit_at(v, i, LIT(kPodTols))) {
    i += sizeof(kPodTols) - 1;
    if (!take(kTols, [&] { return scan_tolerations(v, &i); })) return false;
  }
  if (lit_at(v, i, LIT(kPodAffinity))) {
    i += sizeof(kPodAffinity) - 1;
    if (!take(kAffinity, [&] { return scan_nested(v, &i, '}'); }))
      return false;
  }
  if (lit_at(v, i, LIT(kPodSpread))) {
    i += sizeof(kPodSpread) - 1;
    if (!take(kSpread, [&] { return scan_nested(v, &i, ']'); })) return false;
  }
  // The exact remainder: proves there is no priority and no member out of
  // encode_pod's order.
  return v.size() - i == sizeof(kPodEnd) - 1 && lit_at(v, i, LIT(kPodEnd));
}

#undef LIT

// One event's raw view for the columnar pod-frame emitter (val == null
// or vlen == 0 with etype DELETE means no value).
struct PodEventView {
  uint8_t etype = 0;
  int64_t mrev = 0;
  const char* key = nullptr;
  size_t klen = 0;
  const char* val = nullptr;
  size_t vlen = 0;
};

// Shared by ms_watch_poll_pods (store-side drain) and
// ms_parse_pod_events (wire-side parse): emit the columnar frame
// documented in memstore.h.
template <typename GetView>
uint8_t* emit_pod_frame(size_t n, bool canceled, const uint8_t* sched,
                        size_t sched_len, GetView get, size_t* out_len) {
  std::vector<uint8_t> etype(n), flags(n);
  std::vector<int64_t> mrev(n);
  std::vector<int32_t> cpu(n, 0), mem(n, 0);
  std::vector<uint32_t> shape(n, 0), koff(n + 1, 0), aoff(n + 1, 0);
  std::string keys, aux;
  // The frame's shape table: each distinct quintuple of spans (PodParse)
  // once.  A wave of one template hits `last` every time; the map is only
  // consulted when the shape changes.
  std::string shapes;
  std::vector<uint32_t> soff(1, 0);
  std::unordered_map<std::string, uint32_t> shape_of;
  uint32_t last = 0;
  for (size_t i = 0; i < n; i++) {
    PodEventView ev = get(i);
    etype[i] = ev.etype;
    mrev[i] = ev.mrev;
    keys.append(ev.key, ev.klen);
    koff[i + 1] = static_cast<uint32_t>(keys.size());
    uint8_t f = 0;
    if (ev.etype == 0 && ev.val != nullptr) {
      std::string_view value(ev.val, ev.vlen);
      PodParse p;
      if (parse_pod(value, sched, sched_len, &p)) {
        f |= MS_POD_CANONICAL;
        if (p.sched_match) f |= MS_POD_SCHED_MATCH;
        if (p.has_node) {
          f |= MS_POD_HAS_NODE;
          aux.append(p.node, p.node_len);
        }
        cpu[i] = p.cpu;
        mem[i] = p.mem;
        constexpr int S = kShapeSpans;
        uint32_t len[S];
        uint32_t any = 0;
        for (int j = 0; j < S; j++) {
          len[j] = static_cast<uint32_t>(p.len[j]);
          any |= len[j];
        }
        if (any) {
          bool same = last != 0;
          if (same) {
            const uint32_t* lo = &soff[S * (last - 1)];
            for (int j = 0; same && j < S; j++)
              same = lo[j + 1] - lo[j] == len[j] &&
                     memcmp(shapes.data() + lo[j], p.span[j], len[j]) == 0;
          }
          if (!same) {
            // The leading lengths make the joined spans unambiguous.
            std::string k(reinterpret_cast<const char*>(len), 4 * (S - 1));
            for (int j = 0; j < S; j++) k.append(p.span[j], len[j]);
            auto ins = shape_of.emplace(
                std::move(k), static_cast<uint32_t>(soff.size() / S + 1));
            if (ins.second) {
              for (int j = 0; j < S; j++) {
                shapes.append(p.span[j], len[j]);
                soff.push_back(static_cast<uint32_t>(shapes.size()));
              }
            }
            last = ins.first->second;
          }
          shape[i] = last;
        }
      } else {
        aux.append(value);
      }
    }
    flags[i] = f;
    aoff[i + 1] = static_cast<uint32_t>(aux.size());
  }

  std::string b;
  b.reserve(8 + 2 * n + 8 + 20 * n + 8 * (n + 1) + 4 + 4 * soff.size() +
            keys.size() + aux.size() + shapes.size());
  put_u32(b, static_cast<uint32_t>(n));
  put_u8(b, canceled ? 1 : 0);
  b.append(3, '\0');
  b.append(reinterpret_cast<const char*>(etype.data()), n);
  b.append(reinterpret_cast<const char*>(flags.data()), n);
  b.append((8 - (b.size() % 8)) % 8, '\0');
  b.append(reinterpret_cast<const char*>(mrev.data()), 8 * n);
  b.append(reinterpret_cast<const char*>(cpu.data()), 4 * n);
  b.append(reinterpret_cast<const char*>(mem.data()), 4 * n);
  b.append(reinterpret_cast<const char*>(shape.data()), 4 * n);
  b.append(reinterpret_cast<const char*>(koff.data()), 4 * (n + 1));
  b.append(reinterpret_cast<const char*>(aoff.data()), 4 * (n + 1));
  put_u32(b, static_cast<uint32_t>(soff.size() / kShapeSpans));
  b.append(reinterpret_cast<const char*>(soff.data()), 4 * soff.size());
  b.append(keys);
  b.append(aux);
  b.append(shapes);
  return to_malloc(b, out_len);
}

}  // namespace

int ms_watch_poll_pods(ms_store* s, int64_t watcher_id, int max_events,
                       const uint8_t* sched, size_t sched_len, uint8_t** out,
                       size_t* out_len) {
  std::shared_ptr<Watcher> w;
  {
    RGuard g(s, ms_store::M_WATCH);
    auto it = s->watchers.find(watcher_id);
    if (it != s->watchers.end()) w = it->second;
  }
  if (!w) return MS_ERR_NOT_FOUND;

  bool canceled;
  const std::deque<Event> events = w->take(max_events, 0, &canceled);

  *out = emit_pod_frame(
      events.size(), canceled, sched, sched_len,
      [&](size_t i) -> PodEventView {
        const Rec& r = *events[i].rec;
        return PodEventView{r.del,         r.mod_rev,
                            r.key->data,   r.key->len,
                            r.del ? nullptr : r.val,
                            r.vlen};
      },
      out_len);
  return static_cast<int>(events.size());
}

int ms_parse_pod_events(const uint8_t* buf, size_t len, int n,
                        const uint8_t* sched, size_t sched_len, uint8_t** out,
                        size_t* out_len) {
  if (n < 0) return MS_ERR_INVALID;
  // Validate and index the whole frame first (records:
  // u8 etype | i64 mrev | u32 klen | u32 vlen | key | value).
  std::vector<PodEventView> views;
  views.reserve(n);
  size_t off = 0;
  for (int i = 0; i < n; i++) {
    if (off + 17 > len) return MS_ERR_INVALID;
    PodEventView v{};
    v.etype = buf[off];
    memcpy(&v.mrev, buf + off + 1, 8);
    uint32_t klen, vlen;
    memcpy(&klen, buf + off + 9, 4);
    memcpy(&vlen, buf + off + 13, 4);
    off += 17;
    if (off + klen + vlen > len) return MS_ERR_INVALID;
    v.key = reinterpret_cast<const char*>(buf + off);
    v.klen = klen;
    off += klen;
    v.val = reinterpret_cast<const char*>(buf + off);
    v.vlen = vlen;
    off += vlen;
    views.push_back(v);
  }
  if (off != len) return MS_ERR_INVALID;  // trailing bytes = caller bug
  *out = emit_pod_frame(
      static_cast<size_t>(n), false, sched, sched_len,
      [&](size_t i) { return views[i]; }, out_len);
  return n;
}

int64_t ms_watch_dropped(ms_store* s, int64_t watcher_id) {
  std::shared_lock<std::shared_mutex> g(s->mu);
  auto it = s->watchers.find(watcher_id);
  if (it == s->watchers.end()) return MS_ERR_NOT_FOUND;
  std::lock_guard<std::mutex> g2(it->second->m);
  return it->second->dropped;
}

int64_t ms_watch_pending(ms_store* s, int64_t watcher_id) {
  std::shared_lock<std::shared_mutex> g(s->mu);
  auto it = s->watchers.find(watcher_id);
  if (it == s->watchers.end()) return MS_ERR_NOT_FOUND;
  std::lock_guard<std::mutex> g2(it->second->m);
  return static_cast<int64_t>(it->second->q.size());
}

// ---- stats / maintenance --------------------------------------------------

int64_t ms_num_keys(ms_store* s) {
  return s->live_keys.load(std::memory_order_relaxed);
}

int64_t ms_db_size(ms_store* s) {
  return s->db_bytes.load(std::memory_order_relaxed);
}

int ms_stats_json(ms_store* s, uint8_t** out, size_t* out_len) {
  RGuard g(s, ms_store::M_STATS);
  std::string j = "{\"revision\":" + std::to_string(s->current) +
                  ",\"compact_revision\":" + std::to_string(s->compacted) +
                  ",\"keys\":" + std::to_string(s->live_keys.load()) +
                  ",\"db_bytes\":" + std::to_string(s->db_bytes.load()) +
                  ",\"watchers\":" + std::to_string(s->watchers.size()) +
                  ",\"locks\":[";
  // (method, structure, rw) lock cells, the reference's
  // mem_etcd_lock_seconds/lock_count label set (metrics.rs:78-94).
  bool lfirst = true;
  for (int m = 0; m < ms_store::M_METHODS; m++) {
    for (int rw = 0; rw < 2; rw++) {
      int64_t c = s->lock_count[m][rw].load(std::memory_order_relaxed);
      if (c == 0) continue;
      if (!lfirst) j += ",";
      lfirst = false;
      j += std::string("{\"method\":\"") + ms_store::kMethodNames[m] +
           "\",\"structure\":\"store_mu\",\"rw\":\"" +
           (rw ? "write" : "read") + "\",\"count\":" + std::to_string(c) +
           ",\"wait_ns\":" +
           std::to_string(
               s->lock_wait_ns[m][rw].load(std::memory_order_relaxed)) +
           "}";
    }
  }
  if (s->wal) {
    int64_t c = s->wal->append_count.load(std::memory_order_relaxed);
    if (c > 0) {
      if (!lfirst) j += ",";
      lfirst = false;
      j += "{\"method\":\"wal_append\",\"structure\":\"wal_queue\","
           "\"rw\":\"write\",\"count\":" +
           std::to_string(c) + ",\"wait_ns\":" +
           std::to_string(
               s->wal->append_wait_ns.load(std::memory_order_relaxed)) +
           "}";
    }
  }
  j += "],\"watch_pressure\":{\"enqueued\":" +
       std::to_string(s->watch_enqueued.load(std::memory_order_relaxed)) +
       ",\"enqueue_batches\":" +
       std::to_string(
           s->watch_enqueue_batches.load(std::memory_order_relaxed)) +
       ",\"dropped\":" +
       std::to_string(s->watch_dropped_total.load(std::memory_order_relaxed)) +
       ",\"queue_hwm\":" +
       std::to_string(s->watch_queue_hwm.load(std::memory_order_relaxed)) +
       "},\"prefixes\":{";
  bool first = true;
  for (auto& [p, st] : s->prefix_stats) {
    if (!first) j += ",";
    first = false;
    std::string esc;
    for (char c : p) {
      if (c == '"' || c == '\\') esc += '\\';
      esc += c;
    }
    j += "\"" + esc + "\":{\"keys\":" + std::to_string(st.keys) +
         ",\"bytes\":" + std::to_string(st.bytes) + "}";
  }
  j += "}}";
  *out = to_malloc(j, out_len);
  return MS_OK;
}

int ms_wal_sync(ms_store* s) {
  if (!s->wal) return MS_OK;
  return s->wal->Sync();
}
