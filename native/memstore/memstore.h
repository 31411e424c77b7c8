/* memstore — in-memory MVCC key-value store with etcd semantics.
 *
 * TPU-native framework's equivalent of the reference's mem_etcd
 * (reference mem_etcd/src/store.rs, wal.rs, block_deque.rs — Rust).
 * Re-designed, not translated:
 *   - per-Kind ordered maps keyed by the /registry/[group/]kind/ prefix
 *     (same prefix_split insight, reference store.rs:836-863), held in a
 *     sorted map of trees so cross-prefix ranges also work;
 *   - one global revision log (block array) for MVCC time travel
 *     (reference block_deque.rs);
 *   - watch events are enqueued to per-watcher bounded queues *inside* the
 *     write critical section, so they are revision-ordered by construction
 *     — no re-ordering heap or notify thread needed (the reference needs
 *     one because its revision allocation and notification are decoupled,
 *     store.rs:444-533).  The fan-out happens once a frame — a frame is one
 *     write critical section: a batch, or a single set — at its end and
 *     before the store's write lock is released: each matching watcher's
 *     queue is taken once and handed the frame's matching events as one
 *     run, in revision order, so no event is delivered later than the
 *     return of the call that wrote it.  A queue's cap falls event by
 *     event inside a frame: the queue takes the first events of its run
 *     that it has room for, and the rest are counted as dropped;
 *   - a key and a write are each one allocation, shared by reference by
 *     the two indexes, the revision log, the WAL queue, the watchers'
 *     queues and the polls: a queued event keeps its key and value alive
 *     after compaction has dropped the item;
 *   - WAL: per-prefix append-only files, none/buffered/fsync modes, a
 *     background writer batching records, boot-time merge-replay by
 *     revision (reference wal.rs:62-299).
 *
 * The API is a flat C ABI for ctypes; buffers returned by the store are
 * malloc'd copies the caller frees with ms_free.
 */
#ifndef MEMSTORE_H
#define MEMSTORE_H

#include <stddef.h>
#include <stdint.h>

#ifdef __cplusplus
extern "C" {
#endif

typedef struct ms_store ms_store;

/* WAL modes (reference mem_etcd --wal-default, main.rs:60-81). */
enum {
  MS_WAL_NONE = 0,
  MS_WAL_BUFFERED = 1,
  MS_WAL_FSYNC = 2,
};

/* Error codes (negative returns). */
enum {
  MS_OK = 0,
  MS_ERR_CAS = -1,        /* compare failed; see ms_set out params */
  MS_ERR_COMPACTED = -2,  /* revision below compact revision */
  MS_ERR_FUTURE_REV = -3, /* revision above current revision */
  MS_ERR_NOT_FOUND = -4,
  MS_ERR_INVALID = -5,
  MS_ERR_IO = -6,
};

/* Open a store. wal_dir NULL/empty disables the WAL entirely.
 * no_write_prefixes: '\n'-separated list of key prefixes whose writes skip
 * the WAL (reference --wal-no-write-prefix; events/leases at 100K/s need
 * not be durable).  Replays any existing WAL files before returning. */
ms_store* ms_open(const char* wal_dir, int wal_mode,
                  const char* no_write_prefixes);
void ms_close(ms_store* s);

/* Free any buffer returned through an out-parameter. */
void ms_free(void* p);

/* ---- writes ----------------------------------------------------------- */

/* Set or delete (val==NULL) a key, with optional compare-and-swap.
 *
 *   has_req        0: unconditional; 1: CAS
 *   req_is_version 0: compare latest mod_revision == req_val
 *                  1: compare version == req_val   (0 = key must not exist)
 *   lease          lease id recorded on the KV (0 = none)
 *
 * Success: returns the new revision (> 0).
 * CAS failure: returns MS_ERR_CAS and sets *latest_rev_out to the store's
 * current revision; if the key currently exists and cur_out != NULL, a
 * serialized KV record (see layout below) is malloc'd into *cur_out.
 * This is exactly the Txn failure branch payload
 * (reference store.rs:189-382, kv_service.rs:126-337). */
int64_t ms_set(ms_store* s, const uint8_t* key, size_t klen,
               const uint8_t* val, size_t vlen, int has_req,
               int req_is_version, int64_t req_val, int64_t lease,
               int64_t* latest_rev_out, uint8_t** cur_out,
               size_t* cur_len_out);

/* In fsync mode, ms_set returns only after the record is durable. */

/* Non-blocking twin of ms_set for completion-driven servers (the wire
 * front-end): never blocks on WAL durability.  In fsync mode the caller
 * must hold the client's response until ms_wal_persisted_revision()
 * reaches the returned revision — that is what turns N concurrent
 * per-RPC puts into ONE group-committed fsync (the reference gets the
 * same effect from its batched writer threads, wal.rs:173-248). */
int64_t ms_set_nowait(ms_store* s, const uint8_t* key, size_t klen,
                      const uint8_t* val, size_t vlen, int has_req,
                      int req_is_version, int64_t req_val, int64_t lease,
                      int64_t* latest_rev_out, uint8_t** cur_out,
                      size_t* cur_len_out);

/* WAL mode of this store (MS_WAL_*). */
int ms_wal_mode(ms_store* s);

/* Highest revision whose WAL records are durably written (fsync'd in
 * fsync mode; written in buffered mode; 0 when the WAL is disabled). */
int64_t ms_wal_persisted_revision(ms_store* s);

/* Nonzero once a WAL write/fsync has failed; persisted_revision never
 * advances afterwards, so completion-driven callers must fail their
 * held responses instead of waiting. */
int ms_wal_io_error(ms_store* s);

/* Batch write: n records packed as
 *   u32 klen | u32 vlen | key bytes | val bytes
 * with vlen == 0xFFFFFFFF marking a delete.  The whole batch executes
 * under one lock acquisition and one FFI crossing — the amortization the
 * reference gets from gRPC stream batching + per-core WAL writers
 * (reference wal.rs:173-248).  Returns the last allocated revision (or
 * the current revision if the batch allocated none), MS_ERR_INVALID on a
 * malformed buffer (checked whole before anything commits).  In fsync
 * mode, returns after the batch is durable.
 *
 * The batch is one frame: its records commit one by one, exactly as n
 * ms_set calls in this order would (a key may appear twice, keys may come
 * in any order — in key order the ordered index takes them fastest), and
 * its events reach each matching watcher as one run when the last record
 * has committed, before the call returns (see the design note above for
 * where a queue's cap falls). */
int64_t ms_put_batch(ms_store* s, const uint8_t* buf, size_t len, int n,
                     int64_t lease);

/* Batch bind: splice spec.nodeName into stored pod objects under CAS.
 *
 * n records packed as:
 *   i64 required_mod | u32 klen | u32 nlen | key bytes | node name bytes
 *
 * For each record, if the key's latest mod_revision == required_mod and
 * the stored value is in the canonical encoded-pod shape (opens with
 * "spec":{"schedulerName": and contains no "nodeName"), the store writes
 * a new value with "nodeName":"<name>" spliced after "spec":{ — the
 * DefaultBinder's optimistic-concurrency bind collapsed to one native
 * call per wave (reference README.adoc:558-560 semantics).
 *
 * *out is a malloc'd array of n int64 results: new revision (> 0),
 * MS_ERR_CAS (revision mismatch / key absent), or MS_ERR_INVALID (value
 * not spliceable or name needs JSON escaping — caller falls back to its
 * slow path).  Returns the number of successful binds, or MS_ERR_INVALID
 * on a malformed buffer (checked whole before anything commits).  Like
 * ms_put_batch the wave is one frame: per-record CAS and results, one
 * fan-out at its end, inside the write critical section.
 *
 * exclude_watcher (-1 = none): watcher id whose queue should NOT receive
 * the bind events from this wave.  A scheduling coordinator passes its
 * own pod watcher here: it already accounted the binds it just issued,
 * and at 20K+ binds/s the echo events are half the watch firehose.  The
 * reference's scheduler cache solves the same problem by assuming the
 * pod before the informer echo arrives (its informer then dedups against
 * the assumed state); suppressing at the fan-out is the store-native
 * equivalent: the frame's run is not offered to that watcher at all, so
 * it neither queues nor counts as dropped there.  All other watchers
 * observe every event. */
int ms_bind_batch(ms_store* s, const uint8_t* buf, size_t len, int n,
                  int64_t exclude_watcher, int64_t** out);

/* ---- reads ------------------------------------------------------------ */

/* KV record layout inside result buffers (all little-endian):
 *   u32 klen | u32 vlen | i64 create_rev | i64 mod_rev | i64 version
 *   | i64 lease | key bytes | val bytes
 *
 * Range result buffer layout:
 *   i64 header_revision | i64 total_count | u32 n_kvs | u8 more
 *   | n_kvs * KV record
 *
 * Range over [start, end); end NULL/len 0 = single key; end == "\0" (one
 * zero byte) = from start to infinity (etcd convention).  rev 0 = latest.
 * limit 0 = unlimited.  count_only / keys_only as in etcd RangeRequest.
 * Returns MS_OK or MS_ERR_COMPACTED / MS_ERR_FUTURE_REV. */
int ms_range(ms_store* s, const uint8_t* start, size_t start_len,
             const uint8_t* end, size_t end_len, int64_t rev, int64_t limit,
             int count_only, int keys_only, uint8_t** out, size_t* out_len);

int64_t ms_current_revision(ms_store* s);
int64_t ms_compact_revision(ms_store* s);
/* Highest revision whose watch events are fully enqueued (== current
 * revision here, since enqueue happens inside the write lock; the split
 * exists in the reference because its notify path is async,
 * store.rs:528). */
int64_t ms_progress_revision(ms_store* s);

/* ---- compaction ------------------------------------------------------- */

/* Drop value history strictly below rev.  Latest values are untouched.
 * Returns MS_OK, MS_ERR_COMPACTED (rev already compacted) or
 * MS_ERR_FUTURE_REV. */
int ms_compact(ms_store* s, int64_t rev);

/* ---- watches ---------------------------------------------------------- */

/* Create a watcher over [start, end) (end conventions as ms_range).
 * start_rev > 0 replays history from that revision (inclusive); 0 means
 * "from next write".  Events (including the replay) are delivered through
 * ms_watch_poll in revision order.
 * Returns watcher id >= 0, or MS_ERR_COMPACTED (and sets *compact_rev_out)
 * if start_rev is below the compact revision. */
int64_t ms_watch_create(ms_store* s, const uint8_t* start, size_t start_len,
                        const uint8_t* end, size_t end_len, int64_t start_rev,
                        int want_prev_kv, int64_t queue_cap,
                        int64_t* compact_rev_out);

int ms_watch_cancel(ms_store* s, int64_t watcher_id);

/* Poll result buffer layout:
 *   u32 n_events | u8 canceled | n_events * event
 *   event: u8 type (0 PUT, 1 DELETE) | u8 has_prev | KV record
 *          | [prev KV record if has_prev]
 * Blocks up to timeout_ms for at least one event (0 = non-blocking).
 * max_events bounds the batch (like the reference's recv_many(...,1000),
 * watch_service.rs:126-146). Returns number of events, or < 0 on error
 * (MS_ERR_NOT_FOUND for unknown/canceled watcher). */
int ms_watch_poll(ms_store* s, int64_t watcher_id, int max_events,
                  int timeout_ms, uint8_t** out, size_t* out_len);

/* Drain + parse pod events in one call — the scheduling coordinator's
 * intake firehose.  Same queue semantics as ms_watch_poll (non-blocking,
 * max_events bound), but each PUT value in the canonical encoded-pod
 * shape (the exact byte shape this framework's encode_pod emits for a
 * pod whose only free parts are a flat label map, a nodeSelector, a
 * toleration list, an affinity object and a topologySpreadConstraints
 * array, in that order, including both nodeName forms — the restricted
 * fast-parser contract,
 * mirroring how the reference supports exactly the one Txn shape
 * Kubernetes emits, reference kv_service.rs:126-337) is parsed natively,
 * so the consumer never JSON-decodes its own steady-state traffic.
 * None of the five is interpreted (nodeSelector is proven a flat map of
 * strings, as the labels are; affinity only a balanced object and the
 * spread constraints only a balanced array): the frame carries each
 * distinct quintuple of byte spans once, as a shape, and every event the
 * index of its shape, so the consumer decodes a pod template once and not
 * once per pod.  Non-canonical values (a priority, an escape, a member out
 * of order) are returned whole for the caller's full parser.
 *
 * sched/sched_len: expected spec.schedulerName; parsed pods are flagged
 * with MS_POD_SCHED_MATCH when equal.
 *
 * Columnar result buffer layout (little-endian; sections in order):
 *   u32 n | u8 canceled | u8 pad[3]
 *   u8  etype[n]            0 PUT, 1 DELETE
 *   u8  flags[n]            MS_POD_* bits below
 *   u8  pad[(-2n) mod 8]
 *   i64 mod_revision[n]
 *   i32 cpu_milli[n]        0 unless canonical
 *   i32 mem_kib[n]
 *   u32 shape[n]            0 = none of the five spans below (or not
 *                           canonical); s > 0 = shape table entry s-1
 *   u32 key_off[n+1]        offsets into the key blob
 *   u32 aux_off[n+1]        offsets into the aux blob
 *   u32 n_shapes
 *   u32 shape_off[5*n_shapes+1]  offsets into the shape blob: entry s
 *                           holds its labels at [5s, 5s+1), its
 *                           nodeSelector at [5s+1, 5s+2), its tolerations
 *                           at [5s+2, 5s+3), its affinity at [5s+3, 5s+4)
 *                           and its spread constraints at [5s+4, 5s+5)
 *   key blob | aux blob | shape blob
 * aux holds: node name (canonical PUT with nodeName), the whole value
 * (non-canonical PUT), or nothing (canonical PUT without nodeName,
 * DELETE).  A shape's labels, nodeSelector and affinity are the bytes
 * between the braces of metadata.labels, spec.nodeSelector and
 * spec.affinity (the last holds nodeAffinity, podAffinity and
 * podAntiAffinity as they were written), its tolerations and its spread
 * constraints the bytes between the brackets of spec.tolerations and of
 * spec.topologySpreadConstraints; any may be empty, not all five.
 * Returns the event count or MS_ERR_NOT_FOUND. */
int ms_watch_poll_pods(ms_store* s, int64_t watcher_id, int max_events,
                       const uint8_t* sched, size_t sched_len, uint8_t** out,
                       size_t* out_len);

enum {
  MS_POD_CANONICAL = 1,  /* value parsed natively; cpu/mem/flags valid */
  MS_POD_HAS_NODE = 2,   /* spec.nodeName present (aux = node name) */
  MS_POD_SCHED_MATCH = 4 /* spec.schedulerName == sched argument */
};

/* Store-independent variant of the pod-event parse, for events that
 * arrived over the wire (a remote watcher's buffered protobuf events):
 * n input records packed as
 *   u8 etype | i64 mod_revision | u32 klen | u32 vlen | key | value
 * are parsed into the same columnar frame ms_watch_poll_pods emits
 * (canceled always 0).  Returns n or MS_ERR_INVALID on a malformed
 * buffer. */
int ms_parse_pod_events(const uint8_t* buf, size_t len, int n,
                        const uint8_t* sched, size_t sched_len, uint8_t** out,
                        size_t* out_len);

/* Events dropped on this watcher because its queue (10,000 deep, like
 * reference store.rs:27) overflowed; the server should cancel such
 * watchers. */
int64_t ms_watch_dropped(ms_store* s, int64_t watcher_id);

/* Events currently queued on the watcher (without consuming them). */
int64_t ms_watch_pending(ms_store* s, int64_t watcher_id);

/* ---- stats / maintenance --------------------------------------------- */

/* Total live keys. */
int64_t ms_num_keys(ms_store* s);
/* Approximate resident bytes of keys+latest values (db_size analogue). */
int64_t ms_db_size(ms_store* s);
/* JSON object: per-prefix {keys, bytes}, revision, watcher count, lock
 * cells, and watch_pressure {enqueued, enqueue_batches, dropped,
 * queue_hwm}: enqueue_batches counts the times a writer took a watcher's
 * queue, so enqueued / enqueue_batches is the events handed over per
 * acquisition (a frame's length on the batch lanes, 1 on ms_set). */
int ms_stats_json(ms_store* s, uint8_t** out, size_t* out_len);

/* Block until all WAL records at or below the current revision are
 * persisted (flush).  No-op without a WAL. Returns MS_OK / MS_ERR_IO. */
int ms_wal_sync(ms_store* s);

#ifdef __cplusplus
}
#endif

#endif /* MEMSTORE_H */
