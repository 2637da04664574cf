"""Cost model of the I/O-memory-bound MapReduce framework (paper §1.2-1.3).

The paper evaluates algorithms by
  R  -- number of map-shuffle-reduce rounds,
  C  -- communication complexity (total items sent over all rounds),
  t  -- total internal running time (sum over rounds of the max reducer time),
and lower-bounds wall time by

  T = Omega(t + R*L + C/B)

where L is shuffle latency and B shuffle bandwidth.  Every algorithm in
``repro.core`` threads an :class:`MRCost` accumulator so tests and benchmarks
can check the measured R and C against the paper's O(.) bounds, and the
roofline analysis can evaluate T against TPU constants.
"""
from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple, Optional

import jax.numpy as jnp


class RoundStats(NamedTuple):
    """Per-round shuffle observables (Theorem 2.1's send/keep/receive bounds).

    Fields are scalars — jnp arrays on the jit-able backends, numpy scalars on
    the reference backend — so a round program can thread them through
    ``lax.scan`` without host synchronization.
    """

    items_sent: jnp.ndarray      # sum_v |B_v(r)|  (includes keeps)
    max_sent: jnp.ndarray        # max items sent by any node
    max_received: jnp.ndarray    # max items received by any node
    dropped: jnp.ndarray         # items lost to capacity overflow (0 = valid)


class CostAccum(NamedTuple):
    """Functional accumulator of the paper's complexity measures.

    The value-typed counterpart of :class:`MRCost`: every field is a scalar
    array, updates return new values, and the whole tuple is a pytree — so it
    can be carried through ``jax.jit`` / ``lax.scan`` round loops without the
    host round-trips the mutable side channel forced.  ``communication`` and
    ``internal_time`` are float32 (x64 is disabled; int32 would overflow on
    the quadratic brute-force stages), the rest int32.
    """

    rounds: jnp.ndarray
    communication: jnp.ndarray
    internal_time: jnp.ndarray
    max_reducer_io: jnp.ndarray
    dropped: jnp.ndarray

    @staticmethod
    def zero() -> "CostAccum":
        return CostAccum(rounds=jnp.int32(0),
                         communication=jnp.float32(0),
                         internal_time=jnp.float32(0),
                         max_reducer_io=jnp.int32(0),
                         dropped=jnp.int32(0))

    def add_round(self, items_sent, max_io, dropped=0) -> "CostAccum":
        """Record one map-shuffle-reduce round (pure update)."""
        max_io = jnp.asarray(max_io, jnp.int32)
        return CostAccum(
            rounds=(self.rounds + 1).astype(jnp.int32),
            communication=(self.communication
                           + jnp.asarray(items_sent, jnp.float32)),
            internal_time=(self.internal_time
                           + jnp.asarray(max_io, jnp.float32)),
            max_reducer_io=jnp.maximum(self.max_reducer_io, max_io),
            dropped=(self.dropped + jnp.asarray(dropped, jnp.int32)),
        )

    def add_round_stats(self, stats: RoundStats) -> "CostAccum":
        """Record one round from the shuffle's measured :class:`RoundStats`."""
        return self.add_round(
            items_sent=stats.items_sent,
            max_io=jnp.maximum(jnp.asarray(stats.max_sent, jnp.int32),
                               jnp.asarray(stats.max_received, jnp.int32)),
            dropped=stats.dropped)

    def merge_parallel(self, other: "CostAccum") -> "CostAccum":
        """Costs incurred in parallel: rounds/time take the max, comm adds."""
        return CostAccum(
            rounds=jnp.maximum(self.rounds, other.rounds),
            communication=self.communication + other.communication,
            internal_time=jnp.maximum(self.internal_time, other.internal_time),
            max_reducer_io=jnp.maximum(self.max_reducer_io,
                                       other.max_reducer_io),
            dropped=self.dropped + other.dropped,
        )

    def merge_sequential(self, other: "CostAccum") -> "CostAccum":
        return CostAccum(
            rounds=(self.rounds + other.rounds).astype(jnp.int32),
            communication=self.communication + other.communication,
            internal_time=self.internal_time + other.internal_time,
            max_reducer_io=jnp.maximum(self.max_reducer_io,
                                       other.max_reducer_io),
            dropped=self.dropped + other.dropped,
        )

    def to_mrcost(self) -> "MRCost":
        """Host-side reporting adapter (the one synchronization point)."""
        return MRCost(rounds=int(self.rounds),
                      communication=int(self.communication),
                      internal_time=int(self.internal_time),
                      max_reducer_io=int(self.max_reducer_io))


@dataclasses.dataclass
class MRCost:
    """Accumulator for the paper's three complexity measures."""

    rounds: int = 0
    communication: int = 0        # items sent, summed over rounds
    internal_time: int = 0        # sum over rounds of max reducer I/O (t_r >= max n_{r,i})
    max_reducer_io: int = 0       # max_{r,i} n_{r,i}: must stay <= M for validity

    def round(self, items_sent: int, max_io: int) -> None:
        """Record one map-shuffle-reduce round."""
        self.rounds += 1
        self.communication += int(items_sent)
        self.internal_time += int(max_io)
        self.max_reducer_io = max(self.max_reducer_io, int(max_io))

    def merge_parallel(self, other: "MRCost") -> None:
        """Merge a cost incurred *in parallel* with this one (e.g. recursive
        sub-sorts running simultaneously): rounds take the max, communication
        adds."""
        self.rounds = max(self.rounds, other.rounds)
        self.communication += other.communication
        self.internal_time = max(self.internal_time, other.internal_time)
        self.max_reducer_io = max(self.max_reducer_io, other.max_reducer_io)

    def merge_sequential(self, other: "MRCost") -> None:
        self.rounds += other.rounds
        self.communication += other.communication
        self.internal_time += other.internal_time
        self.max_reducer_io = max(self.max_reducer_io, other.max_reducer_io)

    def absorb(self, accum: CostAccum) -> None:
        """Fold a functional :class:`CostAccum` into this reporting object.

        This is the single host-synchronization point for algorithms whose
        round loops run device-side: they accumulate a CostAccum functionally
        and absorb it here once, at the end."""
        self.merge_sequential(accum.to_mrcost())

    @classmethod
    def from_accum(cls, accum: CostAccum) -> "MRCost":
        return accum.to_mrcost()

    def check_io_bound(self, M: int) -> None:
        if self.max_reducer_io > M:
            raise ValueError(
                f"I/O-memory bound violated: reducer I/O {self.max_reducer_io} > M={M}"
            )

    def lower_bound_time(self, *, latency_s: float, bandwidth_items_s: float,
                         item_time_s: float = 1e-9) -> float:
        """Evaluate T = t + R*L + C/B with concrete constants (seconds)."""
        return (self.internal_time * item_time_s
                + self.rounds * latency_s
                + self.communication / bandwidth_items_s)


def log_M(n: int, M: int) -> int:
    """ceil(log_M n) with the paper's convention log_M n >= 1 for n > 1."""
    if n <= 1:
        return 1
    if M < 2:
        raise ValueError("M must be >= 2")
    return max(1, math.ceil(math.log(n) / math.log(M)))


def tree_height(n_leaves: int, d: int) -> int:
    """Height L = ceil(log_d n) of the paper's d-ary trees (root = level 0)."""
    if n_leaves <= 1:
        return 1
    if d < 2:
        raise ValueError("branching factor must be >= 2")
    return max(1, math.ceil(math.log(n_leaves) / math.log(d)))


class DevicePeaks(NamedTuple):
    """Published per-chip peaks of one accelerator kind."""

    flops_bf16: float          # FLOP/s
    hbm_bw: float              # bytes/s
    ici_bw_per_link: float     # bytes/s per link
    hbm_bytes: float


#: Per-chip peaks keyed by ``jax.Device.device_kind``.  TPU v5e: Google Cloud
#: documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s,
#: 1,600 Gbit/s of interconnect over 4 links).
DEVICE_PEAKS = {
    "TPU v5 lite": DevicePeaks(flops_bf16=197e12, hbm_bw=819e9,
                               ici_bw_per_link=50e9, hbm_bytes=16e9),
}

COLLECTIVE_LAUNCH_LATENCY = 1e-6  # ~ "L" for one shuffle hop on ICI


def device_peaks(device_kind: str) -> DevicePeaks:
    """The published peaks of ``device_kind``; an unknown kind is an error,
    never a default."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add them "
            f"to repro.core.costmodel.DEVICE_PEAKS") from None


@dataclasses.dataclass(frozen=True)
class HardwareModel:
    """Maps the paper's (L, B) shuffle network onto a mesh of ``chips``
    accelerators of ``device_kind`` (peaks from :data:`DEVICE_PEAKS`)."""

    chips: int
    device_kind: str
    latency_s: float = COLLECTIVE_LAUNCH_LATENCY

    def __post_init__(self):
        device_peaks(self.device_kind)     # unknown kinds fail here

    @property
    def peaks(self) -> DevicePeaks:
        return device_peaks(self.device_kind)

    def shuffle_time(self, cost: MRCost, bytes_per_item: int = 4) -> float:
        """Paper lower bound T = Omega(t + R*L + C/B) with B = aggregate ICI
        bandwidth and t charged at HBM streaming rate."""
        agg_bw_items = (self.chips * self.peaks.ici_bw_per_link
                        / bytes_per_item)
        t_seconds = cost.internal_time * bytes_per_item / self.peaks.hbm_bw
        return (t_seconds
                + cost.rounds * self.latency_s
                + cost.communication / agg_bw_items)
