"""Segmented-scan Lindley solver: every server's FCFS queue in one pass.

The partitioned fast path (:mod:`repro.core.sharding`) solves per-server
FCFS queues with the Lindley recurrence.  For rows sorted by server key
with per-segment arrivals ``t`` and service demands ``s``, the service
start obeys the segment-reset scan identity::

    start_j = max(t_j,  max_{i <= j, same segment} (t_i - P_i)  +  P_j)

where ``P_j = sum(s_a .. s_{j-1})`` is the within-segment exclusive
prefix of the service demands — a cumulative sum plus a running maximum,
both resetting at segment boundaries.  Until this module, the engine
evaluated that identity through one zero-padded dense ``(n_servers,
longest_queue)`` array: under a skewed key distribution (one hot server
holding most of the stream) ``longest_queue -> n`` and the pad blows up
to ``O(n_servers * n)`` memory — the exact regime (Zipf object
popularity, hot drives) where the simulator must be fastest.

Two backends evaluate the identity over the contiguous flat layout:

``segmented`` (default, numpy)
    Segments are grouped into power-of-two **length buckets** (segment
    length in ``(2^{b-1}, 2^b]`` lands in bucket ``b``), each bucket
    solved as a dense ``(rows_in_bucket, 2^b)`` block.  A bucket's pad
    is < 2x its real rows, so peak scratch is ``O(n)`` no matter how
    skewed the keys are, and the per-bucket math is the *identical*
    sequence of IEEE-754 operations the old padded-dense layout ran
    (row-wise ``cumsum`` / ``maximum.accumulate``) — outputs are
    byte-for-byte the same, which is what lets the differential
    shard-equivalence harness and the golden traces extend over the new
    backend unchanged.  A flat global-cumsum formulation was rejected:
    re-associating the prefix sums changes the low-order float bits and
    would have broken the bit-identity gate.

``pallas``
    The same buckets solved on the device by a grid-blocked Pallas TPU
    kernel (:mod:`repro.kernels.lindley`): rows ride the lane dimension
    and the depth axis is scanned sequentially.  TPUs have no float64,
    so the kernel carries the Lindley *waiting time* in float32 while
    the host keeps arrivals and starts in float64 (``interpret=True``
    off-TPU like every other kernel in the repo).  Its starts are not
    bit-identical to the numpy backends: they lie within the absolute
    bound of :func:`repro.kernels.lindley.error_bound`, which grows
    with a queue's backlog inside one busy period, not with simulated
    time (pinned in ``tests/test_kernels.py``).

``dense``
    The legacy zero-padded ``(n_servers, longest_queue)`` layout, kept
    as the perf baseline ``benchmarks/bench_engine.py`` measures the
    skew speedup against.  ``segmented`` and ``dense`` are
    byte-identical to each other.

Scratch buffers are pooled per process (:data:`_POOL`) and reused across
buckets, shards, and the accel/non-accel solve phases, so a long run
allocates its working set once.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

__all__ = ["BACKENDS", "fcfs_queues", "queue_depth_max",
           "segment_error_bound", "segment_fenceposts", "solve_segments"]

BACKENDS = ("segmented", "pallas", "dense")

# Reusable scratch: name -> grow-only 1D float64 buffer.  Forked shard
# workers each inherit (copy-on-write) and then own their pool, so the
# drive phase and the CPU phase of one worker share one working set.
_POOL: Dict[str, np.ndarray] = {}


def _scratch(name: str, size: int) -> np.ndarray:
    buf = _POOL.get(name)
    if buf is None or buf.size < size:
        buf = np.empty(max(size, 1), dtype=np.float64)
        _POOL[name] = buf
    return buf


def segment_fenceposts(keys: np.ndarray, lo: int, hi: int) -> np.ndarray:
    """``n_servers + 1`` fenceposts into ``keys`` (sorted server ids in
    ``[lo, hi)``): server ``j``'s rows are ``[seg[j], seg[j+1])``."""
    return np.searchsorted(keys, np.arange(lo, hi + 1))


def _dense_layout(seg: np.ndarray, t: np.ndarray, s: np.ndarray):
    """One ``(n_servers, longest)`` zero-padded block per column (pads
    sit after each row's data, so row-wise prefix scans never see them)
    plus the ``(rows, pos)`` gather indices back to the flat layout."""
    lens = np.diff(seg)
    nserv = lens.size
    rows = np.repeat(np.arange(nserv), lens)
    pos = np.arange(t.size) - np.repeat(seg[:-1], lens)
    shape = (nserv, int(lens.max()))
    T = np.zeros(shape)
    S = np.zeros(shape)
    T[rows, pos] = t
    S[rows, pos] = s
    return T, S, rows, pos


def _solve_dense(seg: np.ndarray, t: np.ndarray, s: np.ndarray,
                 start: np.ndarray) -> None:
    """Legacy padded-dense evaluation over :func:`_dense_layout`."""
    T, S, rows, pos = _dense_layout(seg, t, s)
    C = np.cumsum(S, axis=1)
    prev = C - S
    M = np.maximum.accumulate(T - prev, axis=1)
    start[:] = np.maximum(T, M + prev)[rows, pos]


def _bucket_rows(lens: np.ndarray):
    """Group nonempty segments into power-of-two length buckets.

    Returns ``(order, bounds, widths)``: ``order`` lists segment indices
    sorted by bucket, ``bounds`` are fenceposts into ``order`` per
    bucket, ``widths[b]`` is the bucket's padded row width (< 2x the
    shortest member, so bucket scratch is < 2x its real row count).
    """
    ne = np.flatnonzero(lens)
    if not ne.size:
        z = np.zeros(0, dtype=np.int64)
        return z, np.zeros(1, dtype=np.int64), z
    # bucket id = ceil(log2(len)): len in (2^{b-1}, 2^b] -> width 2^b
    b = np.asarray([(int(v) - 1).bit_length() for v in lens[ne]],
                   dtype=np.int64)
    srt = np.argsort(b, kind="stable")
    order, bs = ne[srt], b[srt]
    cut = np.flatnonzero(np.diff(bs)) + 1
    bounds = np.concatenate([[0], cut, [order.size]]).astype(np.int64)
    widths = (np.int64(1) << bs[bounds[:-1]]).astype(np.int64)
    return order, bounds, widths


def _solve_segmented(seg: np.ndarray, t: np.ndarray, s: np.ndarray,
                     start: np.ndarray, pallas: bool = False) -> None:
    """Bucketed evaluation over the flat layout; fills ``start``."""
    lens = np.diff(seg)
    order, bounds, widths = _bucket_rows(lens)
    for bi in range(bounds.size - 1):
        rows = order[bounds[bi]:bounds[bi + 1]]
        w = int(widths[bi])
        r = rows.size
        rl = lens[rows]
        mass = int(rl.sum())
        # flat gather indices for this bucket's rows
        rr = np.repeat(np.arange(r), rl)
        pp = np.arange(mass) - np.repeat(np.cumsum(rl) - rl, rl)
        flat = np.repeat(seg[:-1][rows], rl) + pp
        T = _scratch("T", r * w)[:r * w].reshape(r, w)
        S = _scratch("S", r * w)[:r * w].reshape(r, w)
        # pads sit after each row's data; garbage there never reaches a
        # real row's prefix, so only the data region is written
        T.fill(0.0)
        S.fill(0.0)
        T[rr, pp] = t[flat]
        S[rr, pp] = s[flat]
        if pallas:
            from repro.kernels import ops
            st = np.asarray(ops.lindley(T, S))
            start[flat] = st[rr, pp]
            continue
        C = _scratch("C", r * w)[:r * w].reshape(r, w)
        P = _scratch("P", r * w)[:r * w].reshape(r, w)
        np.cumsum(S, axis=1, out=C)
        np.subtract(C, S, out=P)             # P = within-segment prefix
        np.subtract(T, P, out=C)             # C := T - P (C is free)
        np.maximum.accumulate(C, axis=1, out=C)   # running max, resets/row
        np.add(C, P, out=C)
        np.maximum(T, C, out=C)              # start, padded layout
        start[flat] = C[rr, pp]


def solve_segments(seg: np.ndarray, t: np.ndarray, s: np.ndarray,
                   start: np.ndarray, fin: np.ndarray, *,
                   backend: str = "segmented") -> None:
    """Fill ``start``/``fin`` for every segment's FCFS queue.

    ``seg`` are :func:`segment_fenceposts`; ``t`` (sorted per segment)
    and ``s`` are the flat arrival/service columns.  ``segmented`` and
    ``dense`` produce byte-identical results; ``pallas`` agrees with
    them within its float32 error bound (see the module docstring).
    """
    if backend not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}, "
                         f"got {backend!r}")
    if not t.size:
        return
    if backend == "dense":
        _solve_dense(seg, t, s, start)
    else:
        _solve_segmented(seg, t, s, start, pallas=(backend == "pallas"))
    np.add(start, s, out=fin)


def segment_error_bound(seg: np.ndarray, t: np.ndarray,
                        s: np.ndarray) -> np.ndarray:
    """Per-row bound on how far the ``pallas`` backend's ``start`` (and
    ``fin``) may lie from the float64 backends' for the flat layout of
    :func:`solve_segments` — :func:`repro.kernels.lindley.error_bound`
    over :func:`_dense_layout`."""
    from repro.kernels.lindley import error_bound
    if not t.size:
        return np.zeros(0)
    T, S, rows, pos = _dense_layout(seg, t, s)
    return error_bound(T, S)[rows, pos]


def fcfs_queues(key: np.ndarray, arrival: np.ndarray, finish: np.ndarray,
                n_servers: int):
    """Rebuild the FCFS queues behind per-request columns of one server
    class: ``key`` (server id), ``arrival`` (when the copy joined) and
    ``finish`` (NaN where the request sent no copy).  Queues are ordered
    by ``(arrival, request id)``, as the partitioned path solves them;
    each copy started at ``max(arrival, previous finish)``.

    Returns ``(rids, seg, t, s, start)`` in queue order, ``seg`` being
    :func:`segment_fenceposts` — the inputs :func:`solve_segments` saw.
    """
    rid = np.flatnonzero(~np.isnan(finish))
    rids = rid[np.lexsort((rid, arrival[rid], key[rid]))]
    seg = segment_fenceposts(key[rids], 0, n_servers)
    t = arrival[rids]
    fin = finish[rids]
    prev = np.empty_like(fin)
    prev[1:] = fin[:-1]
    prev[seg[:-1][np.diff(seg) > 0]] = -np.inf
    start = np.maximum(t, prev)
    return rids, seg, t, fin - start, start


def queue_depth_max(seg: np.ndarray, start: np.ndarray,
                    t: np.ndarray) -> List[int]:
    """Per-segment max queued-copy depth, vectorized across segments.

    Depth is sampled at arrivals (it only grows there): at the ``j``-th
    arrival of a segment the depth is ``j + 1`` minus the number of
    copies already started (``start_i <= t_j``).  Both ``start`` and
    ``t`` are non-decreasing within a segment, so the count is a merge
    rank: sort ``(segment, value, kind)`` with starts ordered before
    arrivals on ties (the ``side='right'`` convention) and count starts
    by cumulative sum — exact, comparison-only, no per-server loop.
    Nonempty segments are pinned to depth >= 1 (the classic engine
    counts the in-service copy whenever the server dispatched at all).
    """
    nserv = seg.size - 1
    m = int(t.size)
    maxd = [0] * nserv
    if not m:
        return maxd
    lens = np.diff(seg)
    seg_id = np.repeat(np.arange(nserv, dtype=np.int64), lens)
    val = np.concatenate([start, t])
    kind = np.zeros(2 * m, dtype=np.int8)
    kind[m:] = 1                            # starts sort before ties
    sid2 = np.concatenate([seg_id, seg_id])
    order = np.lexsort((kind, val, sid2))
    started_cum = np.cumsum(order < m)      # starts seen so far, merged
    p = np.flatnonzero(order >= m)          # merged positions of arrivals
    j = order[p] - m                        # flat arrival index
    depth = np.empty(m, dtype=np.int64)
    depth[j] = j + 1 - started_cum[p]
    ne = np.flatnonzero(lens)
    md = np.maximum.reduceat(depth, seg[:-1][ne]) if ne.size else ne
    for k, d in zip(ne.tolist(), np.maximum(md, 1).tolist()):
        maxd[k] = int(d)
    return maxd
