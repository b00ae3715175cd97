"""Streaming percentile timers: fixed-bucket log-scale histograms.

The PR-2 ingestion counters report interval MEANS (``on_ingest_drain``
sums a latency and divides at log time) — which is exactly the statistic
that hides the tail a pipeline stall lives in (Podracer, arXiv
2104.06272, reports per-stage tails for the same reason). A histogram
with geometrically-spaced buckets gives P50/P95/P99 at a fixed, tiny
cost: one integer increment per observation on the hot path, 64 int64
buckets per stage, and MERGEABILITY — counts from every actor process
add elementwise, so one fleet-wide percentile falls out of summing rows
of the shared-memory board (board.py). Resolution is the bucket growth
factor (~33% here: 8 buckets per decade over 1 µs .. 100 s), plenty for
"P99 queue wait jumped 10x", useless for microbenchmarks —
benchmarks/run.py keeps exact timing.
"""

import math
from typing import Dict, List, Optional

import numpy as np

# Bucket layout — shared by every histogram in the system (local timers,
# the shm board, and the aggregated record all speak this layout, so
# merging is elementwise addition everywhere). Changing it invalidates
# in-flight boards; bump with care.
NBUCKETS = 64
_LO = 1e-6                  # left edge of bucket 0: 1 µs
_DECADES = 8.0              # span: 1 µs .. 100 s
_STEP = _DECADES / NBUCKETS  # log10 width of one bucket (0.125 -> ~33%/bucket)
_INV_STEP = 1.0 / _STEP
_LOG_LO = math.log10(_LO)

# Public aliases for the bucket layout — the device-side bucketize-scatter
# below reproduces bucket_index() inside jit and MUST use the exact same
# constants (parity-tested device vs host).
BUCKET_LO = _LO
BUCKET_LOG_LO = _LOG_LO
BUCKET_INV_STEP = _INV_STEP


# ---------------------------------------------------------------------------
# Device-side twin (jnp; traced into fused steps). ONE implementation of
# the bucketize-scatter shared by the learning diagnostics
# (telemetry/learning.py) and the replay diagnostics
# (telemetry/replaydiag.py) — a third per-pillar copy of the layout math
# would be a parity bug waiting to happen (ISSUE 10 satellite).


def bucketize_values(x):
    """jit twin of bucket_index over |x|: (same-shape) int32 bucket
    indices into the shared 64-bucket log layout. Non-finite values clamp
    into the TOP bucket (the pillars also count them separately) so the
    scatter index stays in range."""
    import jax.numpy as jnp
    ax = jnp.abs(x).astype(jnp.float32)
    i = jnp.floor((jnp.log10(jnp.maximum(ax, BUCKET_LO)) - BUCKET_LOG_LO)
                  * BUCKET_INV_STEP).astype(jnp.int32)
    i = jnp.where(jnp.isfinite(ax), i, NBUCKETS - 1)
    return jnp.clip(i, 0, NBUCKETS - 1)


def value_counts(x, mask=None):
    """(NBUCKETS,) int32 histogram of |x| via bucketize + scatter-add —
    the device-side histogram primitive. ``mask`` (same shape, 0/1)
    excludes padded entries."""
    import jax.numpy as jnp
    idx = bucketize_values(x).reshape(-1)
    ones = (jnp.ones_like(idx) if mask is None
            else mask.reshape(-1).astype(jnp.int32))
    return jnp.zeros((NBUCKETS,), jnp.int32).at[idx].add(ones)


def bucket_index(seconds: float) -> int:
    """Bucket for one duration; durations outside [1 µs, 100 s) clamp to
    the end buckets (they still count, with saturated resolution)."""
    if seconds <= _LO:
        return 0
    i = int((math.log10(seconds) - _LOG_LO) * _INV_STEP)
    return NBUCKETS - 1 if i >= NBUCKETS else i


def value_counts_np(x: np.ndarray, mask=None) -> np.ndarray:
    """Vectorized numpy twin of :func:`value_counts` (same layout, same
    clamping): one log10 + bincount instead of a per-element Python loop
    — what host-side consumers over many values use (HostReplay's leaf
    histogram runs under the replay lock, where a 10^4-iteration Python
    loop would stall sample()/add() every flush)."""
    ax = np.abs(np.asarray(x, np.float64)).reshape(-1)
    # invalid too: floor(NaN).astype(int) warns before the isfinite
    # fallback below replaces the index
    with np.errstate(divide="ignore", invalid="ignore"):
        i = np.floor((np.log10(np.maximum(ax, _LO)) - _LOG_LO)
                     * _INV_STEP).astype(np.int64)
    i = np.where(np.isfinite(ax), i, NBUCKETS - 1)
    i = np.clip(i, 0, NBUCKETS - 1)
    if mask is not None:
        i = i[np.asarray(mask, bool).reshape(-1)]
    return np.bincount(i, minlength=NBUCKETS).astype(np.int64)


def bucket_bounds(i: int) -> tuple:
    """(lo, hi) seconds covered by bucket ``i``."""
    return (10.0 ** (_LOG_LO + i * _STEP), 10.0 ** (_LOG_LO + (i + 1) * _STEP))


def bucket_mid(i: int) -> float:
    """Geometric midpoint of bucket ``i`` — the value a percentile
    reports for observations landing there."""
    return 10.0 ** (_LOG_LO + (i + 0.5) * _STEP)


def percentile(counts: np.ndarray, q: float) -> Optional[float]:
    """The q-quantile (0 < q <= 1) of a counts vector, as the geometric
    midpoint of the bucket where the cumulative count crosses q * total.
    None for an empty histogram."""
    total = int(counts.sum())
    if total == 0:
        return None
    target = q * total
    cum = 0
    for i in range(len(counts)):
        cum += int(counts[i])
        if cum >= target:
            return bucket_mid(i)
    return bucket_mid(len(counts) - 1)


def summarize(counts: np.ndarray) -> Optional[Dict[str, float]]:
    """The aggregated-record entry for one stage: count + P50/P95/P99 in
    milliseconds (rounded to the layout's real resolution). None when the
    interval saw no observations — the stage key is then omitted from the
    record rather than emitting nulls."""
    total = int(counts.sum())
    if total == 0:
        return None
    out = {"count": total}
    for name, q in (("p50_ms", 0.50), ("p95_ms", 0.95), ("p99_ms", 0.99)):
        out[name] = round(percentile(counts, q) * 1e3, 4)
    return out


def value_summary(counts: np.ndarray) -> Optional[Dict[str, float]]:
    """summarize() twin for VALUE-domain histograms (|TD error|, priority,
    |Q| — the learning-diagnostics histograms reuse the duration layout's
    bucket edges, reading 1e-6..100 as raw magnitudes instead of seconds):
    count + P50/P95/P99 in raw units, no ms scaling. None when empty."""
    total = int(np.asarray(counts).sum())
    if total == 0:
        return None
    out = {"count": total}
    for name, q in (("p50", 0.50), ("p95", 0.95), ("p99", 0.99)):
        # 6 significant digits (values span 1e-6..100 — fixed-decimal
        # rounding would flatten the small-magnitude buckets)
        out[name] = float(f"{percentile(np.asarray(counts), q):.6g}")
    return out


class LogHistogram:
    """One stage's histogram — a thin wrapper over the shared bucket
    layout for unit tests and ad-hoc use; the runtime's StageTimers keeps
    a (stages, buckets) matrix directly (core.py)."""

    def __init__(self, counts: Optional[np.ndarray] = None):
        self.counts = (np.zeros(NBUCKETS, np.int64) if counts is None
                       else np.asarray(counts, np.int64).copy())
        if self.counts.shape != (NBUCKETS,):
            raise ValueError(
                f"histogram counts must have shape ({NBUCKETS},), got "
                f"{self.counts.shape}")

    def add(self, seconds: float) -> None:
        self.counts[bucket_index(seconds)] += 1

    def merge(self, other: "LogHistogram") -> "LogHistogram":
        """Elementwise sum — the cross-process aggregation primitive."""
        return LogHistogram(self.counts + other.counts)

    @property
    def total(self) -> int:
        return int(self.counts.sum())

    def percentile(self, q: float) -> Optional[float]:
        return percentile(self.counts, q)

    def summarize(self) -> Optional[Dict[str, float]]:
        return summarize(self.counts)

    def to_list(self) -> List[int]:
        return [int(c) for c in self.counts]
