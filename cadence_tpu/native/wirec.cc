// Native wirec encoder: serialized histories, or a [W, E, L] int64 lane
// tensor the caller already holds, -> adaptive-columnar wirec buffers
// (slab/bases/n_events), byte-identical to ops/wirec.py pack_wirec.
//
// The reference does its hot serialization in compiled Go
// (common/persistence/serialization/); this framework's analog is the
// host-side wire encoder that feeds the TPU link (PAPER.md §7: sustaining
// >=16.7M events/s decode+pack is why this is C++, not Python).
//
// THE ROW IS THE UNIT. A workflow's [E, L] row (17.7 KB at E = 123) is
// counted, then measured or emitted, while it is in the cache; every
// entry point is ONE pass of threads over contiguous row blocks:
//
//   measure  — per-lane plan (CONST/ABS/DELTA/TSREL_NZ, GCD scale,
//              minimal byte width): each thread accumulates the 18
//              lanes' statistics of its rows (AccumulateRow), the blocks
//              are merged in row order (MergeStats) and the plan is
//              decided once (FinishLane). The dense entry point
//              (cadence_wirec_measure) and the streamed one
//              (cadence_wirec_measure_blobs: PackOne into a one-row
//              scratch, then the same AccumulateRow) differ only in
//              where the row comes from;
//   emit     — slab/bases/n_events of a row under a (possibly pinned)
//              profile (EmitRow); a row whose values fall outside the
//              pinned widths/scales reports a misfit code the Python
//              binding raises as ProfileMisfit — the exact refit
//              contract of the numpy encoder. Dense: cadence_wirec_emit;
//   fused    — cadence_wirec_pack_fused: wire blobs -> PackOne into the
//              thread's one-row scratch -> EmitRow, so no [W, E, L]
//              tensor exists between the blobs and the ring slot's
//              buffers (native/feeder.py). A chunk with no profile yet
//              (chunk 0, a refit) takes the two streamed passes:
//              measure_blobs, then pack_fused under the fresh plan.
//
// Semantics are exactly ops/wirec.py — including the floor-division
// quotients numpy's `//` produces on the raw pad-row values ABS lanes
// carry (C's truncating `/` would diverge on negative pads), and the
// exactness checks that decide ProfileMisfit. tests/test_native_packer.py
// fuzzes byte-parity against pack_wirec across every bench suite.
//
// Build: native/build.py (g++ -O3 -shared; hashed over wirec.cc AND
// packer.cc because of the include below); loaded via ctypes.

#include "packer.cc"

#include <numeric>

namespace {

// lane kinds (ops/wirec.py)
constexpr int64_t kKindConst = 0;
constexpr int64_t kKindAbs = 1;
constexpr int64_t kKindDelta = 2;
constexpr int64_t kKindTsrelNz = 3;

// misfit reasons, encoded as 1000 + lane * 4 + reason (positive return
// values of the emit entry points; the binding raises ProfileMisfit)
constexpr int64_t kMisfitConst = 0;
constexpr int64_t kMisfitScale = 1;
constexpr int64_t kMisfitWidth = 2;

inline int64_t MisfitCode(int64_t lane, int64_t reason) {
  return 1000 + lane * 4 + reason;
}

// numpy's floor division (`//`): C truncates toward zero instead
inline int64_t FloorDiv(int64_t a, int64_t b) {
  int64_t q = a / b;
  int64_t r = a % b;
  return (r != 0 && ((r < 0) != (b < 0))) ? q - 1 : q;
}

inline int64_t Gcd64(int64_t g, int64_t v) {
  uint64_t a = static_cast<uint64_t>(g);
  uint64_t b = v < 0 ? -static_cast<uint64_t>(v) : static_cast<uint64_t>(v);
  while (b) {
    uint64_t t = a % b;
    a = b;
    b = t;
  }
  return static_cast<int64_t>(a);
}

// minimal little-endian two's-complement byte width holding [lo, hi]
// (ops/wirec.py _width_for)
inline int64_t WidthFor(int64_t lo, int64_t hi) {
  for (int64_t w = 1; w < 8; ++w) {
    int64_t half = int64_t{1} << (8 * w - 1);
    if (-half <= lo && hi < half) return w;
  }
  return 8;
}

inline bool Fits(int64_t code, int64_t width) {
  if (width >= 8) return true;
  int64_t half = int64_t{1} << (8 * width - 1);
  return -half <= code && code < half;
}

// real-row count of one row: numpy counts positive event ids, it does
// not assume a padded tail (ops/wirec.py: (ev[:,:,0] > 0).sum(axis=1))
inline int64_t RowEvents(const int64_t* row, int64_t E, int64_t L) {
  int64_t n = 0;
  for (int64_t e = 0; e < E; ++e) n += row[e * L + kLaneEventId] > 0;
  return n;
}

// Contiguous row blocks, one a thread: fn(t, lo, hi). Returns the number
// of blocks, which is the length of whatever per-block results fn keeps.
template <typename Fn>
int64_t ForRowBlocks(int64_t W, int64_t num_threads, Fn fn) {
  if (num_threads < 1) num_threads = 1;
  if (num_threads > W) num_threads = W > 0 ? W : 1;
  int64_t block = (W + num_threads - 1) / num_threads;
  if (num_threads == 1) {
    fn(int64_t{0}, int64_t{0}, W);
    return 1;
  }
  std::vector<std::thread> threads;
  for (int64_t t = 0; t < num_threads; ++t) {
    int64_t lo = t * block, hi = std::min(W, lo + block);
    if (lo >= hi) break;
    threads.emplace_back(fn, t, lo, hi);
  }
  for (auto& th : threads) th.join();
  return static_cast<int64_t>(threads.size());
}

// ---------------------------------------------------------------------------
// measure: the decision procedure of _plan_lane as accumulate-a-row /
// merge / finish, so a lane's plan needs one visit of each row, whoever
// holds it.
// ---------------------------------------------------------------------------

struct LaneStats {
  bool any = false, all_eq = true, has_zero = false, has_big = false;
  bool any_nz = false;
  int64_t first = 0;
  int64_t min_v = 0, max_v = 0, g_abs = 0;
  int64_t min_d = 0, max_d = 0, g_d = 0;  // d of a row's first event is 0
  int64_t min_r = 0, max_r = 0, g_ts = 0;
};

// every lane's statistics over the first n events of one [E, L] row
void AccumulateRow(const int64_t* row, int64_t n, int64_t L,
                   LaneStats* stats) {
  const int64_t ts_base = row[kLaneTimestamp];  // row 0 timestamp
  for (int64_t lane = 0; lane < L; ++lane) {
    LaneStats s = stats[lane];
    int64_t prev = 0;
    for (int64_t e = 0; e < n; ++e) {
      int64_t v = row[e * L + lane];
      if (!s.any) {
        s.any = true;
        s.first = s.min_v = s.max_v = v;
      } else {
        s.all_eq = s.all_eq && (v == s.first);
        if (v < s.min_v) s.min_v = v;
        if (v > s.max_v) s.max_v = v;
      }
      // gcd(1, x) is 1: most lanes get there within a few events
      if (s.g_abs != 1) s.g_abs = Gcd64(s.g_abs, v);
      if (v == 0) s.has_zero = true;
      if ((v < 0 ? -v : v) > (int64_t{1} << 31)) s.has_big = true;
      int64_t d = (e == 0) ? 0 : v - prev;
      prev = v;
      if (d < s.min_d) s.min_d = d;
      if (d > s.max_d) s.max_d = d;
      if (s.g_d != 1) s.g_d = Gcd64(s.g_d, d);
      if (v != 0) {
        int64_t r = v - ts_base;
        if (!s.any_nz) {
          s.any_nz = true;
          s.min_r = s.max_r = r;
        } else {
          if (r < s.min_r) s.min_r = r;
          if (r > s.max_r) s.max_r = r;
        }
        if (s.g_ts != 1) s.g_ts = Gcd64(s.g_ts, r);
      }
    }
    stats[lane] = s;
  }
}

// fold a LATER block of rows into an earlier one: `first` stays the
// first real value in row-major order
void MergeStats(LaneStats* into, const LaneStats& later) {
  if (!later.any) return;
  if (!into->any) {
    *into = later;
    return;
  }
  LaneStats& s = *into;
  s.all_eq = s.all_eq && later.all_eq && later.first == s.first;
  s.min_v = std::min(s.min_v, later.min_v);
  s.max_v = std::max(s.max_v, later.max_v);
  s.g_abs = Gcd64(s.g_abs, later.g_abs);
  s.has_zero = s.has_zero || later.has_zero;
  s.has_big = s.has_big || later.has_big;
  s.min_d = std::min(s.min_d, later.min_d);
  s.max_d = std::max(s.max_d, later.max_d);
  s.g_d = Gcd64(s.g_d, later.g_d);
  if (later.any_nz) {
    s.min_r = s.any_nz ? std::min(s.min_r, later.min_r) : later.min_r;
    s.max_r = s.any_nz ? std::max(s.max_r, later.max_r) : later.max_r;
    s.any_nz = true;
    s.g_ts = Gcd64(s.g_ts, later.g_ts);
  }
}

void FinishLane(const LaneStats& s, int64_t* kind, int64_t* width,
                int64_t* scale, int64_t* cnst) {
  if (!s.any || s.all_eq) {
    *kind = kKindConst;
    *width = 0;
    *scale = 1;
    *cnst = s.any ? s.first : 0;
    return;
  }
  int64_t g_abs = s.g_abs <= 0 ? 1 : s.g_abs;
  // GCD of |values| divides every value exactly, so / is floor-exact
  int64_t w_abs = WidthFor(s.min_v / g_abs, s.max_v / g_abs);
  int64_t g_d = s.g_d <= 0 ? 1 : s.g_d;
  int64_t w_d = WidthFor(s.min_d / g_d, s.max_d / g_d);

  int64_t best_kind = kKindAbs, best_w = w_abs, best_scale = g_abs;
  if (w_d < w_abs) {
    best_kind = kKindDelta;
    best_w = w_d;
    best_scale = g_d;
  }
  if (s.has_zero && s.has_big && s.any_nz) {
    int64_t g_ts = s.g_ts <= 0 ? 1 : s.g_ts;
    int64_t q_min = s.min_r / g_ts, q_max = s.max_r / g_ts;
    int64_t code_lo = q_min < 0 ? q_min : 0;
    int64_t code_hi = q_max + 1 > 0 ? q_max + 1 : 0;
    int64_t w_ts = WidthFor(code_lo, code_hi);
    if (w_ts < best_w || (best_kind == kKindDelta && w_ts == best_w)) {
      best_kind = kKindTsrelNz;
      best_w = w_ts;
      best_scale = g_ts;
    }
  }
  *kind = best_kind;
  *width = best_w;
  *scale = best_scale;
  *cnst = 0;
}

// per-block statistics, merged in row order, to kinds/widths/scales/consts
void FinishPlan(const std::vector<std::vector<LaneStats>>& blocks,
                int64_t n_blocks,
                int64_t L, int64_t* kinds, int64_t* widths, int64_t* scales,
                int64_t* consts) {
  for (int64_t lane = 0; lane < L; ++lane) {
    LaneStats s = blocks[0][static_cast<size_t>(lane)];
    for (int64_t b = 1; b < n_blocks; ++b) {
      MergeStats(&s, blocks[static_cast<size_t>(b)][static_cast<size_t>(lane)]);
    }
    FinishLane(s, &kinds[lane], &widths[lane], &scales[lane], &consts[lane]);
  }
}

// ---------------------------------------------------------------------------
// emit: one workflow row under the profile. Returns 0 or a misfit code:
// the first lane of the profile that does not fit, and within a lane a
// scale misfit before a width overflow — the order pack_wirec checks in.
// Every slab byte / bases column of the row is written, so preallocated
// buffers need no zeroing between chunks.
// ---------------------------------------------------------------------------

struct LanePlan {
  int64_t lane, kind, offset, width, scale, cnst, base_index;
};

// `width` little-endian bytes of a code; false when the code needs more
inline bool PutCode(uint8_t* out, int64_t c, int64_t width) {
  uint64_t u = static_cast<uint64_t>(c);
  switch (width) {
    case 1:
      out[0] = static_cast<uint8_t>(u);
      break;
    case 2:
      out[0] = static_cast<uint8_t>(u);
      out[1] = static_cast<uint8_t>(u >> 8);
      break;
    default:
      for (int64_t k = 0; k < width; ++k)
        out[k] = static_cast<uint8_t>(u >> (8 * k));
  }
  return Fits(c, width);
}

int64_t EmitRow(const int64_t* row, int64_t E, int64_t L, int64_t n,
                const LanePlan* profile, int64_t P, int64_t B,
                uint8_t* srow, int64_t* brow) {
  const int64_t ts_base = row[kLaneTimestamp];
  for (int64_t p = 0; p < P; ++p) {
    const LanePlan& pl = profile[p];
    const int64_t* col = row + pl.lane;
    if (pl.kind == kKindConst) {
      bool same = true;
      for (int64_t e = 0; e < n; ++e) same &= col[e * L] == pl.cnst;
      if (!same) return MisfitCode(pl.lane, kMisfitConst);
      continue;
    }
    uint8_t* out = srow + pl.offset;
    const int64_t width = pl.width, scale = pl.scale;
    // one pass a lane: the code, its exactness on real rows, its fit at
    // the pinned width (pad codes included) and its bytes
    bool exact = true, fits = true;
    if (pl.kind == kKindAbs) {
      // numpy `v // scale` floors; pad rows carry raw values (0/-1)
      if (scale == 1) {
        for (int64_t e = 0; e < E; ++e)
          fits &= PutCode(out + e * B, col[e * L], width);
      } else {
        for (int64_t e = 0; e < E; ++e) {
          int64_t v = col[e * L];
          int64_t c = FloorDiv(v, scale);
          exact &= e >= n || c * scale == v;
          fits &= PutCode(out + e * B, c, width);
        }
      }
    } else if (pl.kind == kKindDelta) {
      int64_t prev = 0;
      for (int64_t e = 0; e < E; ++e) {
        int64_t v = col[e * L];
        int64_t d = (e == 0 || e >= n) ? 0 : v - prev;
        prev = v;
        int64_t c = scale != 1 ? FloorDiv(d, scale) : d;
        exact &= c * scale == d;
        fits &= PutCode(out + e * B, c, width);
      }
      if (pl.base_index >= 0) brow[pl.base_index] = col[0];
    } else {  // kKindTsrelNz
      for (int64_t e = 0; e < E; ++e) {
        int64_t v = col[e * L];
        int64_t c = 0;
        if (e < n && v != 0) {
          int64_t q = FloorDiv(v - ts_base, scale);
          c = q >= 0 ? q + 1 : q;
          // undo the zero-escape bias and demand exactness (the
          // pinned-profile refit signal, scale 1 included)
          int64_t m = c - (c >= 1 ? 1 : 0);
          exact &= m * scale + ts_base == v;
        }
        fits &= PutCode(out + e * B, c, width);
      }
      if (pl.base_index >= 0) brow[pl.base_index] = ts_base;
    }
    if (!exact) return MisfitCode(pl.lane, kMisfitScale);
    if (!fits) return MisfitCode(pl.lane, kMisfitWidth);
  }
  return 0;
}

// what one thread's block of rows came to: events decoded, the first
// decode failure (-(w+1)*1000 - err) and the first misfit code
struct BlockResult {
  int64_t total = 0, err = 0, misfit = 0;
};

// A pass's verdict from its blocks in row order: the lowest workflow's
// decode failure, else the events decoded; *misfit is the lowest row's
// misfit code (0 = clean). A decode failure outranks a misfit.
int64_t Verdict(const std::vector<BlockResult>& res, int64_t n_blocks,
                int64_t* misfit) {
  int64_t total = 0;
  *misfit = 0;
  for (int64_t b = n_blocks - 1; b >= 0; --b) {
    const BlockResult& r = res[static_cast<size_t>(b)];
    if (r.misfit != 0) *misfit = r.misfit;
    total += r.total;
  }
  for (int64_t b = 0; b < n_blocks; ++b) {
    if (res[static_cast<size_t>(b)].err != 0)
      return res[static_cast<size_t>(b)].err;
  }
  return total;
}

std::vector<LanePlan> BuildProfile(const int64_t* p_lane,
                                   const int64_t* p_kind,
                                   const int64_t* p_offset,
                                   const int64_t* p_width,
                                   const int64_t* p_scale,
                                   const int64_t* p_const,
                                   const int64_t* p_base_index,
                                   int64_t P) {
  std::vector<LanePlan> prof(static_cast<size_t>(P));
  for (int64_t p = 0; p < P; ++p) {
    prof[static_cast<size_t>(p)] =
        LanePlan{p_lane[p], p_kind[p], p_offset[p], p_width[p],
                 p_scale[p], p_const[p], p_base_index[p]};
  }
  return prof;
}

}  // namespace

extern "C" {

// Per-lane plan of a [W, E, L] int64 lane tensor: writes kinds/widths/
// scales/consts[L]. The binding assembles offsets/base columns with the
// same loop pack_wirec uses, so the profile STRUCTURE can never drift.
int64_t cadence_wirec_measure(const int64_t* lanes, int64_t W, int64_t E,
                              int64_t L, int64_t* kinds, int64_t* widths,
                              int64_t* scales, int64_t* consts,
                              int64_t num_threads) {
  std::vector<std::vector<LaneStats>> blocks(
      static_cast<size_t>(std::max<int64_t>(num_threads, 1)),
      std::vector<LaneStats>(static_cast<size_t>(L)));
  int64_t n_blocks = ForRowBlocks(
      W, num_threads, [&](int64_t t, int64_t lo, int64_t hi) {
        LaneStats* stats = blocks[static_cast<size_t>(t)].data();
        for (int64_t w = lo; w < hi; ++w) {
          const int64_t* row = lanes + w * E * L;
          AccumulateRow(row, RowEvents(row, E, L), L, stats);
        }
      });
  FinishPlan(blocks, n_blocks, L, kinds, widths, scales, consts);
  return 0;
}

// The same plan from W serialized histories (offsets has W + 1 entries
// into blob), with no lane tensor: each thread decodes a row into its
// one-row scratch and accumulates it there. Returns the total events
// decoded, or the packer's -(workflow+1)*1000 - err on the lowest
// workflow that fails to decode.
int64_t cadence_wirec_measure_blobs(const uint8_t* blob,
                                    const int64_t* offsets, int64_t W,
                                    int64_t E, int64_t L, int64_t* kinds,
                                    int64_t* widths, int64_t* scales,
                                    int64_t* consts, int64_t num_threads) {
  size_t slots = static_cast<size_t>(std::max<int64_t>(num_threads, 1));
  std::vector<std::vector<LaneStats>> blocks(
      slots, std::vector<LaneStats>(static_cast<size_t>(L)));
  std::vector<BlockResult> res(slots);
  int64_t n_blocks = ForRowBlocks(
      W, num_threads, [&](int64_t t, int64_t lo, int64_t hi) {
        LaneStats* stats = blocks[static_cast<size_t>(t)].data();
        BlockResult& r = res[static_cast<size_t>(t)];
        std::vector<int64_t> row(static_cast<size_t>(E * L));
        for (int64_t w = lo; w < hi; ++w) {
          int64_t n = PackOne(blob + offsets[w], offsets[w + 1] - offsets[w],
                              E, L, row.data());
          if (n < 0) {
            r.err = -(w + 1) * 1000 + n;
            return;
          }
          r.total += n;
          AccumulateRow(row.data(), RowEvents(row.data(), n, L), L, stats);
        }
      });
  int64_t misfit;
  int64_t total = Verdict(res, n_blocks, &misfit);
  if (total >= 0)
    FinishPlan(blocks, n_blocks, L, kinds, widths, scales, consts);
  return total;
}

// Emit a [W, E, L] lane tensor under a pinned profile (7 parallel arrays
// of P entries). Returns 0, or 1000 + lane*4 + reason on a profile
// misfit (the binding raises ProfileMisfit — measured, never silent).
int64_t cadence_wirec_emit(const int64_t* lanes, int64_t W, int64_t E,
                           int64_t L,
                           const int64_t* p_lane, const int64_t* p_kind,
                           const int64_t* p_offset, const int64_t* p_width,
                           const int64_t* p_scale, const int64_t* p_const,
                           const int64_t* p_base_index, int64_t P,
                           int64_t B, int64_t K,
                           uint8_t* slab, int64_t* bases, int32_t* n_events,
                           int64_t num_threads) {
  auto prof = BuildProfile(p_lane, p_kind, p_offset, p_width, p_scale,
                           p_const, p_base_index, P);
  std::vector<BlockResult> res(
      static_cast<size_t>(std::max<int64_t>(num_threads, 1)));
  int64_t n_blocks = ForRowBlocks(
      W, num_threads, [&](int64_t t, int64_t lo, int64_t hi) {
        BlockResult& r = res[static_cast<size_t>(t)];
        for (int64_t w = lo; w < hi && r.misfit == 0; ++w) {
          const int64_t* row = lanes + w * E * L;
          int64_t n = RowEvents(row, E, L);
          n_events[w] = static_cast<int32_t>(n);
          r.misfit = EmitRow(row, E, L, n, prof.data(), P, B,
                             slab + w * E * B, bases + w * K);
        }
      });
  int64_t misfit;
  Verdict(res, n_blocks, &misfit);
  return misfit;
}

// The fused streaming chunk: wire blobs -> wirec buffers under a pinned
// profile in one ctypes call and one pass of threads. A thread decodes a
// row into its one-row scratch (PackOne), counts its events and emits it
// into slab[w] / bases[w] before it decodes the next. Returns the total
// events packed, or the packer's -(workflow+1)*1000 - err on the lowest
// workflow that fails to decode; *misfit_out lands the first row's emit
// misfit code (0 = clean). A block that has seen a misfit goes on
// decoding: a chunk with both faults reports the decode failure, which
// no refit would cure.
int64_t cadence_wirec_pack_fused(
    const uint8_t* blob, const int64_t* offsets, int64_t W, int64_t E,
    int64_t L,
    const int64_t* p_lane, const int64_t* p_kind, const int64_t* p_offset,
    const int64_t* p_width, const int64_t* p_scale, const int64_t* p_const,
    const int64_t* p_base_index, int64_t P, int64_t B, int64_t K,
    uint8_t* slab, int64_t* bases, int32_t* n_events, int64_t* misfit_out,
    int64_t num_threads) {
  auto prof = BuildProfile(p_lane, p_kind, p_offset, p_width, p_scale,
                           p_const, p_base_index, P);
  std::vector<BlockResult> res(
      static_cast<size_t>(std::max<int64_t>(num_threads, 1)));
  int64_t n_blocks = ForRowBlocks(
      W, num_threads, [&](int64_t t, int64_t lo, int64_t hi) {
        BlockResult& r = res[static_cast<size_t>(t)];
        std::vector<int64_t> row(static_cast<size_t>(E * L));
        for (int64_t w = lo; w < hi; ++w) {
          int64_t n = PackOne(blob + offsets[w], offsets[w + 1] - offsets[w],
                              E, L, row.data());
          if (n < 0) {
            r.err = -(w + 1) * 1000 + n;
            return;
          }
          r.total += n;
          if (r.misfit != 0) continue;
          int64_t real = RowEvents(row.data(), n, L);
          n_events[w] = static_cast<int32_t>(real);
          r.misfit = EmitRow(row.data(), E, L, real, prof.data(), P, B,
                             slab + w * E * B, bases + w * K);
        }
      });
  return Verdict(res, n_blocks, misfit_out);
}

}  // extern "C"
