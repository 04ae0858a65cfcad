// dryad_io — native host-side IO engine for dryad_tpu.
//
// TPU-native counterpart of the reference's native channel/buffer layer
// (reference DryadVertex/VertexHost: channelbuffernativereader.cpp /
// channelbuffernativewriter.cpp — double-buffered async file IO on an IO
// completion port (dryadnativeport.cpp:345-391) — and the DrMemoryStream
// growable buffer streams).  On a TPU host the data plane's hot host-side
// work is (a) packing variable-length records into fixed-shape tensors and
// (b) bulk scatter-gather file IO for spill/store; both are implemented
// here natively with a worker-thread pool, called from Python via ctypes
// (no pybind11 in this environment).
//
// Build: make -C native   (g++ -O3 -shared -fPIC)

#include <algorithm>
#include <atomic>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <thread>
#include <vector>

#ifdef __linux__
#include <sched.h>
#endif
#include <fcntl.h>
#include <unistd.h>
#include <zlib.h>

extern "C" {

// ---------------------------------------------------------------------------
// Record packing: newline-delimited text -> padded [cap, max_len] u8 matrix
// + lengths.  (The vectorized-ingest role of the reference's
// DryadLinqTextReader / LineRecord byte-stream parsing.)
//
// Returns number of lines packed, or -1 if cap was exceeded (caller
// re-sizes).  Lines longer than max_len are truncated (semantic match with
// StringColumn).  A trailing line without '\n' counts.
int64_t dryad_pack_lines(const uint8_t* buf, int64_t len, int64_t max_len,
                         uint8_t* out_data, int32_t* out_lens, int64_t cap) {
  int64_t n = 0;
  int64_t start = 0;
  for (int64_t i = 0; i <= len; ++i) {
    if (i == len || buf[i] == '\n') {
      if (i == len && i == start) break;  // no trailing empty line
      int64_t l = i - start;
      if (l > 0 && buf[start + l - 1] == '\r') --l;  // CRLF
      if (n >= cap) return -1;
      int64_t keep = l < max_len ? l : max_len;
      std::memcpy(out_data + n * max_len, buf + start, (size_t)keep);
      if (keep < max_len)
        std::memset(out_data + n * max_len + keep, 0, (size_t)(max_len - keep));
      out_lens[n] = (int32_t)keep;
      ++n;
      start = i + 1;
    }
  }
  return n;
}

// Pack a list of byte strings (ptrs+lens) into a padded matrix.
// Returns n on success, -1 on cap overflow.
int64_t dryad_pack_bytes(const uint8_t** ptrs, const int64_t* lens, int64_t n,
                         int64_t max_len, uint8_t* out_data,
                         int32_t* out_lens, int64_t cap) {
  if (n > cap) return -1;
  for (int64_t i = 0; i < n; ++i) {
    int64_t keep = lens[i] < max_len ? lens[i] : max_len;
    std::memcpy(out_data + i * max_len, ptrs[i], (size_t)keep);
    if (keep < max_len)
      std::memset(out_data + i * max_len + keep, 0, (size_t)(max_len - keep));
    out_lens[i] = (int32_t)keep;
  }
  return n;
}

// ---------------------------------------------------------------------------
// Parallel scatter-gather file IO (the spill/store engine).
//
// Each "file job" is a path plus a list of (ptr, len) segments written (or
// read) contiguously.  Jobs fan out over a thread pool — partitions spill
// in parallel, matching the reference's per-channel async buffer queues
// (channelbufferqueue.cpp) in role.

struct Seg { const uint8_t* ptr; int64_t len; };

static int write_one(const char* path, const Seg* segs, int64_t nsegs) {
  FILE* f = std::fopen(path, "wb");
  if (!f) return -1;
  setvbuf(f, nullptr, _IOFBF, 1 << 20);
  for (int64_t s = 0; s < nsegs; ++s) {
    if (segs[s].len == 0) continue;
    if (std::fwrite(segs[s].ptr, 1, (size_t)segs[s].len, f) !=
        (size_t)segs[s].len) {
      std::fclose(f);
      return -1;
    }
  }
  if (std::fclose(f) != 0) return -1;
  return 0;
}

static int read_one(const char* path, const Seg* segs, int64_t nsegs) {
  FILE* f = std::fopen(path, "rb");
  if (!f) return -1;
  setvbuf(f, nullptr, _IOFBF, 1 << 20);
  for (int64_t s = 0; s < nsegs; ++s) {
    if (segs[s].len == 0) continue;
    if (std::fread((void*)segs[s].ptr, 1, (size_t)segs[s].len, f) !=
        (size_t)segs[s].len) {
      std::fclose(f);
      return -1;
    }
  }
  std::fclose(f);
  return 0;
}

// Ranged read: segment s is the segs[s].len bytes at file offset offs[s] —
// the leaves a read of some columns keeps (io/store.read_parts), wherever
// they lie in the partition file.  pread, so no byte between two kept
// leaves is touched; a read cut short (the file ends inside a range) fails.
static int read_one_ranges(const char* path, const Seg* segs,
                           const int64_t* offs, int64_t nsegs) {
  int fd = ::open(path, O_RDONLY);
  if (fd < 0) return -1;
  for (int64_t s = 0; s < nsegs; ++s) {
    for (int64_t done = 0; done < segs[s].len;) {
      ssize_t r = ::pread(fd, (void*)(segs[s].ptr + done),
                          (size_t)(segs[s].len - done),
                          (off_t)(offs[s] + done));
      if (r <= 0) {
        ::close(fd);
        return -1;
      }
      done += r;
    }
  }
  ::close(fd);
  return 0;
}

// gzip variants (level-1 deflate): the per-channel compression transform of
// the reference (GzipCompressionChannelTransform.cpp; job-level intermediate
// compression mode, GraphManager DrGraph.cpp:47).
// gz IO takes unsigned (32-bit) lengths: loop in <=256MB slices so
// segments >= 2 GB neither truncate nor wrap the success check.
static const int64_t kGzSlice = 1LL << 28;

static int write_one_gz(const char* path, const Seg* segs, int64_t nsegs) {
  gzFile f = gzopen(path, "wb1");
  if (!f) return -1;
  gzbuffer(f, 1 << 20);
  for (int64_t s = 0; s < nsegs; ++s) {
    for (int64_t off = 0; off < segs[s].len; off += kGzSlice) {
      int64_t n = segs[s].len - off;
      if (n > kGzSlice) n = kGzSlice;
      if (gzwrite(f, segs[s].ptr + off, (unsigned)n) != (int)n) {
        gzclose(f);
        return -1;
      }
    }
  }
  return gzclose(f) == Z_OK ? 0 : -1;
}

static int read_one_gz(const char* path, const Seg* segs, int64_t nsegs) {
  gzFile f = gzopen(path, "rb");
  if (!f) return -1;
  gzbuffer(f, 1 << 20);
  for (int64_t s = 0; s < nsegs; ++s) {
    for (int64_t off = 0; off < segs[s].len; off += kGzSlice) {
      int64_t n = segs[s].len - off;
      if (n > kGzSlice) n = kGzSlice;
      if (gzread(f, (void*)(segs[s].ptr + off), (unsigned)n) != (int)n) {
        gzclose(f);
        return -1;
      }
    }
  }
  gzclose(f);
  return 0;
}

// One job a file on a pool of nthreads workers.  mode: 0 = read, 1 = write,
// 2 = read gzip, 3 = write gzip; seg_file_offs (mode 0 only, else null):
// the file offset each segment is read from.  Returns 0 on success, else
// the (1-based) index of the first failed job.
static int64_t run_file_jobs(const char** paths, int64_t n,
                             const uint8_t** seg_ptrs,
                             const int64_t* seg_lens,
                             const int64_t* seg_file_offs,
                             const int64_t* seg_offsets, int32_t mode,
                             int32_t nthreads) {
  if (nthreads < 1) nthreads = 1;
  if (nthreads > 64) nthreads = 64;
  std::atomic<int64_t> next(0), failed(0);
  auto worker = [&]() {
    while (true) {
      int64_t i = next.fetch_add(1);
      if (i >= n || failed.load() != 0) break;
      int64_t s0 = seg_offsets[i], s1 = seg_offsets[i + 1];
      std::vector<Seg> segs;
      segs.reserve((size_t)(s1 - s0));
      for (int64_t s = s0; s < s1; ++s)
        segs.push_back(Seg{seg_ptrs[s], seg_lens[s]});
      int rc;
      switch (mode) {
        case 1: rc = write_one(paths[i], segs.data(),
                               (int64_t)segs.size()); break;
        case 2: rc = read_one_gz(paths[i], segs.data(),
                                 (int64_t)segs.size()); break;
        case 3: rc = write_one_gz(paths[i], segs.data(),
                                  (int64_t)segs.size()); break;
        default: rc = seg_file_offs
            ? read_one_ranges(paths[i], segs.data(), seg_file_offs + s0,
                              (int64_t)segs.size())
            : read_one(paths[i], segs.data(), (int64_t)segs.size());
      }
      if (rc != 0) failed.store(i + 1);
    }
  };
  std::vector<std::thread> pool;
  int nt = (int)(nthreads < n ? nthreads : n);
  for (int t = 0; t < nt; ++t) pool.emplace_back(worker);
  for (auto& th : pool) th.join();
  return failed.load();
}

// paths: array of n C strings; seg_offsets: n+1 prefix offsets into the
// flat segs arrays.  Each file's segments are written (or read)
// contiguously from its first byte.
int64_t dryad_file_jobs(const char** paths, int64_t n,
                        const uint8_t** seg_ptrs, const int64_t* seg_lens,
                        const int64_t* seg_offsets, int32_t mode,
                        int32_t nthreads) {
  return run_file_jobs(paths, n, seg_ptrs, seg_lens, nullptr, seg_offsets,
                       mode, nthreads);
}

// The ranged form of a read: flat segment s of file i is the seg_lens[s]
// bytes at file offset seg_file_offs[s].
int64_t dryad_read_ranges(const char** paths, int64_t n,
                          const uint8_t** seg_ptrs, const int64_t* seg_lens,
                          const int64_t* seg_file_offs,
                          const int64_t* seg_offsets, int32_t nthreads) {
  return run_file_jobs(paths, n, seg_ptrs, seg_lens, seg_file_offs,
                       seg_offsets, 0, nthreads);
}

// ---------------------------------------------------------------------------
// Row compaction: padded [n, max_len] byte matrix + lengths -> contiguous
// packed bytes + (n+1) offsets.  The egress mirror of dryad_pack_bytes:
// collect()'s string columns compact here in one native pass instead of
// copying per-row padding through Python (the reference streams records out
// through DryadLinqBinaryWriter; our egress is a single packed buffer).
// Returns total packed bytes.
int64_t dryad_compact_rows(const uint8_t* data, const int32_t* lens,
                           int64_t n, int64_t max_len, uint8_t* out,
                           int64_t* out_offs) {
  int64_t o = 0;
  out_offs[0] = 0;
  for (int64_t i = 0; i < n; ++i) {
    int64_t l = lens[i];
    if (l < 0) l = 0;
    if (l > max_len) l = max_len;
    std::memcpy(out + o, data + i * max_len, (size_t)l);
    o += l;
    out_offs[i + 1] = o;
  }
  return o;
}

// ---------------------------------------------------------------------------
// 64-bit FNV-1a, chained (host-side content fingerprinting for store
// integrity — the role of the reference's Rabin fingerprints, classlib
// fingerprint.cpp).  The store's first digest (manifest form "fnv64") is
// ONE such chain over a partition's segments: pass the previous return as
// `seed`, starting from the FNV basis.  One xor and one multiply a byte,
// each waiting for the one before.
uint64_t dryad_fingerprint_seed(const uint8_t* buf, int64_t len,
                                uint64_t seed) {
  uint64_t h = seed;
  for (int64_t i = 0; i < len; ++i) {
    h ^= buf[i];
    h *= 1099511628211ULL;
  }
  return h;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// The store's digest as independent blocks (manifest form "fnv64-blocks").
//
// A partition's bytes are its leaves in file order; a leaf's bytes are cut
// into blocks of `block` bytes (the last one short).  Each block is digested
// byte-wise by FNV-1a from the basis — the kernel above — so no block waits
// for another: a worker runs kDigestLanes of them in lockstep (one chain
// leaves three of the multiplier's four pipeline slots empty), and workers
// run side by side.  A leaf's digest is FNV-1a over its block digests as
// 8-byte little-endian words, a partition's the same over its leaf digests.
//
// The bytes arrive as segments cut anywhere (the chunks a column came off
// the device in, one array a column, one blob): the digest is a function of
// the leaves' bytes and the block size only, a block may straddle segments,
// and nothing is copied to make it contiguous.

static const uint64_t kFnvBasis = 0xCBF29CE484222325ULL;
static const uint64_t kFnvPrime = 0x100000001B3ULL;
static const int kDigestLanes = 4;

struct DigestBlock { int64_t seg, off, len; };  // first byte: segs[seg] + off

struct DigestJob {
  const uint8_t** seg_ptrs;
  const int64_t* seg_lens;
  const DigestBlock* blocks;
  int64_t nblocks;
  std::atomic<int64_t> next{0};
  uint64_t* out;  // one digest a block
};

struct DigestLane {
  const uint8_t* p;
  int64_t piece_left, block_left, seg, id;
  uint64_t h;
};

// Point the lane at its next bytes: the rest of its block in the next
// segment, else (its digest stored) the next block nobody has taken.
// False when there is none left.
static bool digest_advance(DigestJob& job, DigestLane& l, bool fresh) {
  if (!fresh && l.block_left > 0) {
    do { ++l.seg; } while (job.seg_lens[l.seg] == 0);
    l.p = job.seg_ptrs[l.seg];
    l.piece_left = std::min(job.seg_lens[l.seg], l.block_left);
    return true;
  }
  if (!fresh) job.out[l.id] = l.h;
  int64_t b = job.next.fetch_add(1);
  if (b >= job.nblocks) return false;
  const DigestBlock& blk = job.blocks[b];
  l.id = b;
  l.h = kFnvBasis;
  l.seg = blk.seg;
  l.p = job.seg_ptrs[blk.seg] + blk.off;
  l.block_left = blk.len;
  l.piece_left = std::min(job.seg_lens[blk.seg] - blk.off, blk.len);
  return true;
}

template <int K>
static inline void digest_lockstep(DigestLane* l, int64_t n) {
  uint64_t h[K];
  const uint8_t* p[K];
  for (int k = 0; k < K; ++k) { h[k] = l[k].h; p[k] = l[k].p; }
  for (int64_t i = 0; i < n; ++i)
    for (int k = 0; k < K; ++k) h[k] = (h[k] ^ p[k][i]) * kFnvPrime;
  for (int k = 0; k < K; ++k) {
    l[k].h = h[k];
    l[k].p = p[k] + n;
    l[k].piece_left -= n;
    l[k].block_left -= n;
  }
}

static void digest_worker(DigestJob& job) {
  const int K = kDigestLanes;
  DigestLane lane[K];
  int active = 0;
  while (active < K && digest_advance(job, lane[active], true)) ++active;
  while (active == K) {
    int64_t n = lane[0].piece_left;
    for (int k = 1; k < K; ++k) n = std::min(n, lane[k].piece_left);
    digest_lockstep<K>(lane, n);
    int kept = 0;
    for (int k = 0; k < K; ++k)
      if (lane[k].piece_left > 0 || digest_advance(job, lane[k], false))
        lane[kept++] = lane[k];
    active = kept;
  }
  // no block left to take: finish the ones in hand, one at a time
  for (int k = 0; k < active; ++k)
    do {
      digest_lockstep<1>(&lane[k], lane[k].piece_left);
    } while (digest_advance(job, lane[k], false));
}

// Workers a process may run at once, sized once from the cores it may use
// (capped as dryad_file_jobs caps its own).  Calls that overlap — a service
// running several jobs — share it: each takes what is free and no more.
static int digest_pool_size() {
  static const int n = [] {
    int c = 0;
#ifdef __linux__
    cpu_set_t set;
    if (sched_getaffinity(0, sizeof(set), &set) == 0) c = CPU_COUNT(&set);
#endif
    if (c < 1) c = (int)std::thread::hardware_concurrency();
    return c < 1 ? 1 : (c > 64 ? 64 : c);
  }();
  return n;
}
static std::atomic<int> g_digest_helpers(0);

static uint64_t fnv_words(const uint64_t* w, int64_t n) {
  uint64_t h = kFnvBasis;
  for (int64_t i = 0; i < n; ++i)
    for (int b = 0; b < 8; ++b) {
      h ^= (w[i] >> (8 * b)) & 0xff;
      h *= kFnvPrime;
    }
  return h;
}

extern "C" {

// Digest nparts partitions in one call.  Partition p's bytes are the flat
// segments [part_seg_offsets[p], part_seg_offsets[p+1]) end to end, and
// its leaves have the byte lengths leaf_lens[part_leaf_offsets[p] ..
// part_leaf_offsets[p+1]).  Writes one digest a leaf (flat) and one a
// partition; stats = {blocks, threads}.  Returns 0, or p + 1 when
// partition p's segments do not hold exactly its leaves' bytes (nothing
// is digested then), or -1 for a block size below 1.
int64_t dryad_digest_parts(const uint8_t** seg_ptrs, const int64_t* seg_lens,
                           const int64_t* part_seg_offsets,
                           const int64_t* leaf_lens,
                           const int64_t* part_leaf_offsets, int64_t nparts,
                           int64_t block, uint64_t* leaf_out,
                           uint64_t* part_out, int64_t* stats) {
  if (block < 1) return -1;
  std::vector<DigestBlock> blocks;
  std::vector<int64_t> leaf_first;  // a leaf's first block; one past the end
  int64_t total = 0;
  for (int64_t p = 0; p < nparts; ++p) {
    int64_t seg = part_seg_offsets[p], seg_end = part_seg_offsets[p + 1];
    int64_t have = 0, want = 0;
    for (int64_t s = seg; s < seg_end; ++s) have += seg_lens[s];
    for (int64_t l = part_leaf_offsets[p]; l < part_leaf_offsets[p + 1]; ++l)
      want += leaf_lens[l];
    if (have != want) return p + 1;
    total += have;
    int64_t off = 0;
    for (int64_t l = part_leaf_offsets[p]; l < part_leaf_offsets[p + 1]; ++l) {
      leaf_first.push_back((int64_t)blocks.size());
      for (int64_t left = leaf_lens[l]; left > 0;) {
        while (off == seg_lens[seg]) { ++seg; off = 0; }
        int64_t len = std::min(block, left);
        blocks.push_back(DigestBlock{seg, off, len});
        left -= len;
        for (int64_t skip = len; skip > 0;) {  // may leave off == seg's length
          int64_t take = std::min(seg_lens[seg] - off, skip);
          off += take;
          skip -= take;
          if (skip > 0) { ++seg; off = 0; }
        }
      }
    }
  }
  leaf_first.push_back((int64_t)blocks.size());

  std::vector<uint64_t> block_out(blocks.size());
  DigestJob job;
  job.seg_ptrs = seg_ptrs;
  job.seg_lens = seg_lens;
  job.blocks = blocks.data();
  job.nblocks = (int64_t)blocks.size();
  job.out = block_out.data();
  // a worker for every kDigestLanes blocks and every MiB; the caller is the
  // first, so a small input (a streamed chunk) starts no thread
  int64_t want = std::min((job.nblocks + kDigestLanes - 1) / kDigestLanes,
                          total >> 20) - 1;
  int helpers = 0;
  for (int used = g_digest_helpers.load();;) {
    int64_t free_now = digest_pool_size() - 1 - used;
    helpers = (int)std::max<int64_t>(0, std::min(want, free_now));
    if (g_digest_helpers.compare_exchange_weak(used, used + helpers)) break;
  }
  std::vector<std::thread> pool;
  for (int t = 0; t < helpers; ++t)
    pool.emplace_back(digest_worker, std::ref(job));
  digest_worker(job);
  for (auto& th : pool) th.join();
  g_digest_helpers.fetch_sub(helpers);

  int64_t leaf = 0;
  for (int64_t p = 0; p < nparts; ++p) {
    int64_t l0 = part_leaf_offsets[p], l1 = part_leaf_offsets[p + 1];
    for (int64_t l = l0; l < l1; ++l, ++leaf)
      leaf_out[l] = fnv_words(block_out.data() + leaf_first[leaf],
                              leaf_first[leaf + 1] - leaf_first[leaf]);
    part_out[p] = fnv_words(leaf_out + l0, l1 - l0);
  }
  stats[0] = job.nblocks;
  stats[1] = helpers + 1;
  return 0;
}

}  // extern "C"
