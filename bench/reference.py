"""Plain reference for the benchmark's correctness check.

Written from DAMOV's definitions (arXiv:2105.03725, sections 2-3) and the
configuration files beside it.  It imports nothing of the program under
test and is kept deliberately slow and obvious: one Python loop per cache
level over line addresses, one dict per cache set.

- :func:`lru_level` / :func:`simulate` -- set-associative LRU caches with
  64-byte lines, looked up level by level (a level sees the misses of the
  level above it), the shared last level scaled by the thread's share.
- :func:`synthetic_trace` -- the seven DAMOV access-pattern families, one
  per-thread word-address trace per (entry, cores, seed).
- :func:`temporal_locality` and :func:`classify` -- DAMOV Eq. 2 and the
  section 3.3 decision procedure with the paper's thresholds.
- :func:`window_words` -- a window of a model step's HBM word stream:
  each MXU-tiled matmul block by block in the pipeline's fetch/write-back
  order (:func:`dense_walk`), each whole-array op read and written whole
  (:func:`stream_walk`).  The ops' places (kind, operand shapes, bases,
  elements per word, length) come from the capture; an op of another kind
  (a Pallas kernel) is fed in as the program walked it.

``approximate_level`` is the control: it decides a hit by the number of
accesses to the set since the line's last use (not the number of distinct
lines, LRU's stack distance), and only within a capped window.  Those are
the shortcuts a faster scan could take, and the check must reject them.
"""

from __future__ import annotations

import math
import zlib
from collections import Counter

import numpy as np

LINE_BYTES = 64
WORD_BYTES = 8
WORDS_PER_LINE = LINE_BYTES // WORD_BYTES


# --------------------------------------------------------------------------
# Caches.
# --------------------------------------------------------------------------
def level_sets(size_bytes: int, ways: int, share: float = 1.0) -> int:
    """Sets of one level; ``share`` < 1 shrinks a shared last level to the
    thread's part of it, never below one set."""
    if share < 1.0:
        size_bytes = max(LINE_BYTES * ways, int(size_bytes * share))
    return max(1, size_bytes // (LINE_BYTES * ways))


def lru_level(lines, sets: int, ways: int) -> tuple[int, list[int]]:
    """One LRU level over ``lines``: (hits, the miss stream in order).

    A reference to the line used just before it is a hit that leaves the
    set as it is (that line is already the most recent), so such repeats
    are counted before the loop and only the other references walk it."""
    lines = np.asarray(lines, dtype=np.int64)
    if lines.size == 0:
        return 0, []
    new = np.ones(lines.size, dtype=bool)
    new[1:] = lines[1:] != lines[:-1]
    heads = lines[new].tolist()
    table: list[dict] = [{} for _ in range(sets)]
    hits = lines.size - len(heads)
    misses: list[int] = []
    for line in heads:
        s = table[line % sets]
        if line in s:
            del s[line]          # most recently used goes to the end
            s[line] = None
            hits += 1
        else:
            if len(s) == ways:
                del s[next(iter(s))]   # least recently used is first
            s[line] = None
            misses.append(line)
    return hits, misses


def approximate_level(lines, sets: int, ways: int,
                      window: int = 4096) -> tuple[int, list[int]]:
    """The control: a shortcut a faster scan could take.  A hit when the
    line was used within the last ``window`` references of this level's
    stream and fewer than ``ways`` references to its set came in between,
    however many of them repeat a line (slots counted, not distinct
    lines, over a capped window)."""
    lines = np.asarray(lines, dtype=np.int64).tolist()
    clock = [0] * sets
    last: dict[int, tuple[int, int]] = {}     # line -> (position, set clock)
    hits = 0
    misses: list[int] = []
    for pos, line in enumerate(lines):
        s = line % sets
        t = clock[s]
        prev = last.get(line)
        if prev is not None and pos - prev[0] <= window and t - prev[1] < ways:
            hits += 1
        else:
            misses.append(line)
        last[line] = (pos, t + 1)
        clock[s] = t + 1
    return hits, misses


def simulate(addresses: np.ndarray, levels, *, share: float = 1.0,
             level=lru_level,
             memo: dict | None = None) -> tuple[tuple[int, ...],
                                                tuple[int, ...]]:
    """(hits per level, misses per level) of a word-address trace.

    ``levels`` is a sequence of ``(size_bytes, ways)`` from the first level
    down; the last of two or more is shared and gets ``share`` of it.
    ``memo`` (a dict the caller keeps) holds the private levels' result
    per trace content, so a trace run again with another share replays
    only the shared level."""
    addresses = np.ascontiguousarray(addresses, dtype=np.int64)
    private = len(levels) - 1 if len(levels) >= 2 else len(levels)
    key = (zlib.crc32(addresses), addresses.size, tuple(levels), level)
    got = memo.get(key) if memo is not None else None
    if got is None:
        stream = addresses // WORDS_PER_LINE
        hits, misses = [], []
        for size, ways in levels[:private]:
            h, stream = level(stream, level_sets(size, ways), ways)
            hits.append(h)
            misses.append(len(stream))
        got = (hits, misses, stream)
        if memo is not None:
            memo[key] = got
    hits, misses, stream = list(got[0]), list(got[1]), got[2]
    for size, ways in levels[private:]:
        h, out = level(stream, level_sets(size, ways, share), ways)
        hits.append(h)
        misses.append(len(out))
    return tuple(hits), tuple(misses)


# --------------------------------------------------------------------------
# Synthetic DAMOV families (per-thread word-address traces).
# --------------------------------------------------------------------------
_L1_WORDS = 32 * 1024 // WORD_BYTES
_HOT_WORDS = 2048                    # 16 KiB of locals
_MIB_WORDS = 2**20 // WORD_BYTES


def name_seed(name: str) -> int:
    """Per-entry offset of the trace generator's seed (crc32, so it does
    not change from one interpreter to the next)."""
    return zlib.crc32(name.encode("utf-8")) % 7919


def _mix(hot: np.ndarray, cold: np.ndarray, every: int) -> np.ndarray:
    """One ``cold`` word every ``every`` references, ``hot`` elsewhere,
    each part repeated cyclically to fill its slots."""
    n = hot.size + cold.size
    out = np.empty(n, dtype=np.int64)
    is_cold = np.zeros(n, dtype=bool)
    is_cold[np.arange(0, n, every)[:cold.size]] = True
    out[is_cold] = np.resize(cold, int(is_cold.sum()))
    out[~is_cold] = np.resize(hot, int((~is_cold).sum()))
    return out


def synthetic_trace(entry: dict, cores: int,
                    seed: int) -> tuple[np.ndarray, float]:
    """(per-thread word addresses, share of the shared LLC) of one
    synthetic roster entry at ``cores`` threads."""
    fam, p, n = entry["family"], entry["params"], entry["params"]["refs"]
    rng = np.random.default_rng(seed + name_seed(entry["name"]))
    if fam == "stream":
        words = p["footprint_mib"] * _MIB_WORDS
        start = int(rng.integers(0, 2**28))
        return start + np.arange(n, dtype=np.int64) % max(words, n), 1.0
    if fam == "irregular":
        words = p["footprint_mib"] * _MIB_WORDS
        return rng.integers(0, words, size=n, dtype=np.int64), 1.0
    if fam == "chase":
        words, every = p["footprint_mib"] * _MIB_WORDS, p["cold_every"]
        cold = rng.integers(_HOT_WORDS, words, size=n // every,
                            dtype=np.int64)
        hot = rng.integers(0, _HOT_WORDS, size=n - n // every,
                           dtype=np.int64)
        return _mix(hot, cold, every), 1.0
    if fam == "blocked":
        n, every = p["trace_refs"], 8
        tile_lines = max(p["footprint_mib"] * _MIB_WORDS // cores
                         // WORDS_PER_LINE, 8)
        tile = (np.arange(n // every, dtype=np.int64) % tile_lines
                * WORDS_PER_LINE)
        hot = rng.integers(0, _HOT_WORDS, size=n - n // every,
                           dtype=np.int64)
        return _mix(hot, 2**27 + tile, every), 1.0 / cores
    if fam == "contended":
        lines = p["distinct_lines"]
        pool = rng.integers(0, 4 * lines, size=lines,
                            dtype=np.int64) * WORDS_PER_LINE
        return np.tile(np.repeat(pool, 3), p["sweeps"]), 1.0 / cores
    if fam == "l1cap":
        ws, run, every = int(_L1_WORDS * p["ws_over_l1"]), 9, 6
        n_stream = n // every
        base = rng.integers(0, ws, size=max((n - n_stream) // run, 1),
                            dtype=np.int64)
        hot = np.repeat(base, run)[:n - n_stream]
        stream = 2**27 + np.arange(n_stream, dtype=np.int64)
        return _mix(hot, stream, every), 1.0
    if fam == "gemm":
        block, run = int(_L1_WORDS * p["block_over_l1"]), 9
        base = rng.integers(0, block, size=max(n // run, 1), dtype=np.int64)
        return np.repeat(base, run)[:n], 1.0
    raise ValueError(f"unknown synthetic family {fam!r}")


# --------------------------------------------------------------------------
# DAMOV Step 2 and the six-class decision (section 3.3).
# --------------------------------------------------------------------------
def temporal_locality(addresses: np.ndarray, window: int = 32) -> float:
    """Eq. 2: in each window of ``window`` references, an address used
    ``1 + N`` times (N >= 1) adds ``2**floor(log2 N)``; the sum over all
    windows is divided by the references those windows hold."""
    addr = np.asarray(addresses, dtype=np.int64).tolist()
    n = len(addr)
    if n == 0:
        return 0.0
    if n < window:
        windows, total = [addr], n
    else:
        k = n // window
        windows = [addr[i * window:(i + 1) * window] for i in range(k)]
        total = k * window
    score = 0
    for w in windows:
        for uses in Counter(w).values():
            if uses > 1:
                score += 2 ** int(math.floor(math.log2(uses - 1)))
    return min(score / total, 1.0)


def classify(temporal: float, ai: float, mpki: float, lfmr_slope: float,
             thresholds: dict) -> str:
    """DAMOV's decision procedure: temporal locality splits 1x from 2x,
    then the LFMR trend over the core sweep, MPKI and AI pick the class."""
    if temporal < thresholds["temporal"]:
        if lfmr_slope < -thresholds["slope"]:
            return "1c"
        return "1a" if mpki >= thresholds["mpki"] else "1b"
    if lfmr_slope > thresholds["slope"]:
        return "2a"
    return "2c" if ai >= thresholds["ai"] else "2b"


# --------------------------------------------------------------------------
# The HBM word streams of a model step's ops.
# --------------------------------------------------------------------------
MAX_DOT_STEPS = 8192                    # grid steps of a matmul, at most


def block_words(shape: tuple[int, ...], block: tuple[int, ...],
                index: tuple[int, ...], epw: int) -> np.ndarray:
    """Word offsets of block ``index`` of a row-major array ``shape``:
    the block's rows in order, one word per ``epw`` elements of a row."""
    strides = [1] * len(shape)
    for d in range(len(shape) - 2, -1, -1):
        strides[d] = strides[d + 1] * shape[d + 1]
    rows = np.zeros(1, dtype=np.int64)
    for d in range(len(shape) - 1):
        start = index[d] * block[d]
        rows = (rows[:, None] + np.arange(start, start + block[d],
                                          dtype=np.int64)[None, :]
                * strides[d]).ravel()
    cols = index[-1] * block[-1] + np.arange(0, block[-1], epw,
                                             dtype=np.int64)
    return ((rows[:, None] + cols[None, :]) // epw).ravel()


def mxu_tile(n: int, cap: int = 128) -> int:
    """The largest divisor of ``n`` that is at most ``cap``."""
    t = max(1, min(n, cap))
    while n % t:
        t -= 1
    return t


def dot_tiles(g: int, m: int, k: int, n: int) -> tuple[int, int, int]:
    """Blocks ``(bm, bn, bk)`` of a matmul on the MXU: 128-wide tiles; past
    ``MAX_DOT_STEPS`` grid steps the whole of k per tile, then tiles up to
    1024 wide, then the whole arrays in one step."""
    bm, bn, bk = mxu_tile(m), mxu_tile(n), mxu_tile(k)
    if g * (m // bm) * (n // bn) * (k // bk) > MAX_DOT_STEPS:
        bk = k
        if g * (m // bm) * (n // bn) > MAX_DOT_STEPS:
            bm, bn = mxu_tile(m, 1024), mxu_tile(n, 1024)
            if g * (m // bm) * (n // bn) > MAX_DOT_STEPS:
                bm, bn = m, n
    return bm, bn, bk


def dense_walk(op: dict):
    """Yield the word blocks of ``out[g, m, n] = lhs[g, m, k] @ rhs[g, k,
    n]`` over the grid ``(g, m/bm, n/bn, k/bk)``, k innermost.  At each
    step the lhs block ``(g, i, kk)`` and the rhs block ``(g, kk, j)`` are
    fetched when they differ from the previous step's, and the out block
    ``(g, i, j)`` is written back when the next step moves to another (or
    the grid ends)."""
    ops = {o["name"]: o for o in op["operands"]}
    lhs, rhs, out = ops["lhs"], ops["rhs"], ops["out"]
    g, m, k = lhs["shape"]
    n = rhs["shape"][2]
    bm, bn, bk = dot_tiles(g, m, k, n)
    base = op["bases"]
    steps = [(gg, i, j, kk) for gg in range(g) for i in range(m // bm)
             for j in range(n // bn) for kk in range(k // bk)]
    prev_l = prev_r = None
    for s, (gg, i, j, kk) in enumerate(steps):
        if (gg, i, kk) != prev_l:
            yield base["lhs"] + block_words((g, m, k), (1, bm, bk),
                                            (gg, i, kk), lhs["epw"])
            prev_l = (gg, i, kk)
        if (gg, kk, j) != prev_r:
            yield base["rhs"] + block_words((g, k, n), (1, bk, bn),
                                            (gg, kk, j), rhs["epw"])
            prev_r = (gg, kk, j)
        nxt = steps[s + 1] if s + 1 < len(steps) else None
        if nxt is None or nxt[:3] != (gg, i, j):
            yield base["out"] + block_words((g, m, n), (1, bm, bn),
                                            (gg, i, j), out["epw"])


def stream_walk(op: dict):
    """Yield the word blocks of a single-step whole-array op: every input
    read whole, in order, then every output written whole."""
    for role in ("in", "out"):
        for o in op["operands"]:
            if o["role"] == role:
                shape = tuple(o["shape"])
                yield op["bases"][o["name"]] + block_words(
                    shape, shape, (0,) * len(shape), o["epw"])


OP_WALKS = {"dense": dense_walk, "stream": stream_walk}


def window_words(ops: list[dict], start: int, end: int,
                 fed: dict[int, np.ndarray]) -> np.ndarray:
    """Words ``start:end`` of a step whose ops are ``ops`` in program order
    (each with its ``refs``).  Ops of the kinds above are generated here;
    ``fed[i]`` holds op ``i``'s part of the window where it is not."""
    pieces, pos = [], 0
    for i, op in enumerate(ops):
        nxt = pos + op["refs"]
        if nxt > start and pos < end:
            if op["kind"] not in OP_WALKS:
                pieces.append(fed[i])
            else:
                at = pos
                for blk in OP_WALKS[op["kind"]](op):
                    lo, hi = at, at + blk.size
                    if hi > start and lo < end:
                        pieces.append(blk[max(0, start - lo):end - lo])
                    at = hi
                    if at >= end:
                        break
        pos = nxt
        if pos >= end:
            break
    return (np.concatenate(pieces) if pieces
            else np.empty(0, dtype=np.int64))
