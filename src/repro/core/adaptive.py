"""Adaptive strategy selection (paper §3.2 Discussion + Table 1).

The paper recommends choosing the update method per query from optimizer
statistics (cardinality, skew), with thread-local as the safe default
("if implementers were to only choose one method ... choose fully concurrent
aggregation with thread local updates").  We implement exactly that policy,
with the TPU strategy names, plus a cheap on-sample estimator for when the
optimizer has no statistics.

Decision table (TPU adaptation of paper Table 1):

  cardinality      skew        → ticketing    update        distributed merge
  ---------------------------------------------------------------------------
  tiny (≤ 4k)      any         → hash         onehot (MXU)  dense psum
  low–high         any         → hash         scatter       dense psum
  unique-ish       low         → sort         sort_segment  all_to_all (partitioned)
  unique-ish       heavy       → hash         scatter       dense psum (skew-immune)
  bounded domain   any         → direct       scatter       dense psum
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.core.hashing import EMPTY_KEY, table_capacity


@dataclass(frozen=True)
class WorkloadStats:
    n_rows: int
    est_groups: int           # cardinality estimate (optimizer or sample)
    est_top_freq: float       # estimated frequency of the heaviest key (0..1)
    key_domain: int | None = None  # known bounded domain, if any


@dataclass(frozen=True)
class Plan:
    ticketing: str   # hash | sort | direct
    update: str      # scatter | onehot | sort_segment | serialized
    distributed: str  # dense_psum | all_to_all
    capacity: int    # ticket table capacity (pow2)
    kernel: str | None = None  # fused | None (planner's ExecutionPolicy.kernel pick)


#: VMEM per TensorCore (bytes), keyed by ``jax.Device.device_kind``.  Source:
#: ``TpuInfo.vmem_capacity_bytes`` in jax 0.9.0
#: (``jax/_src/pallas/mosaic/tpu_info.py``).  The fused kernel must co-house
#: its table, ticket map and accumulators with the morsel blocks and
#: compiler scratch, so the planner only claims a quarter.
VMEM_BYTES = {
    "TPU v4": 16 * 1024 * 1024,
    "TPU v5 lite": 128 * 1024 * 1024,
    "TPU v5e": 128 * 1024 * 1024,
    "TPU v5": 64 * 1024 * 1024,
    "TPU v5p": 64 * 1024 * 1024,
    "TPU v6 lite": 128 * 1024 * 1024,
}


def vmem_bytes(device_kind: str) -> int:
    """VMEM per core of a TPU ``device_kind``; an unknown kind is an error,
    never a guess."""
    try:
        return VMEM_BYTES[device_kind]
    except KeyError:
        raise ValueError(
            f"no VMEM size recorded for TPU device_kind {device_kind!r}; "
            f"known kinds: {sorted(VMEM_BYTES)}"
        ) from None


def fused_table_bytes(est_groups: int, num_accumulators: int = 1,
                      load_factor: float = 0.5) -> int:
    """Device bytes of ONE fused-kernel program's persistent state at a
    group bound: open-addressed table (keys + tickets, int32 each at
    ``capacity = est_groups / load_factor`` rounded to pow2), the
    ticket→key map, and one float32 accumulator row per ``AggSpec``
    accumulator (mean counts twice: sum + count)."""
    cap = table_capacity(max(est_groups, 1), load_factor)
    return 8 * cap + 4 * est_groups + 4 * num_accumulators * est_groups


def kernel_table_budget() -> int:
    """VMEM bytes the planner lets a fused table claim: a quarter of the
    device's VMEM on TPU, 0 elsewhere — in interpret mode the fused route is
    correct but has no residency advantage, so off-TPU plans keep the scan
    pipeline unless the caller sets ``ExecutionPolicy.kernel`` (or a
    ``vmem_budget``) explicitly."""
    if jax.default_backend() != "tpu":
        return 0
    return vmem_bytes(jax.devices()[0].device_kind) // 4


def choose_plan(stats: WorkloadStats, *, num_accumulators: int = 1,
                vmem_budget: int | None = None) -> Plan:
    from repro.kernels.ops import backend_refusal

    unique_frac = stats.est_groups / max(stats.n_rows, 1)
    heavy = stats.est_top_freq >= 0.25
    cap = table_capacity(stats.est_groups)
    budget = kernel_table_budget() if vmem_budget is None else vmem_budget
    # bound the fused fit check at the 2× headroom the resolver actually
    # binds, so a fused pick doesn't immediately outgrow VMEM; never pick a
    # kernel this backend's compiler refuses
    fused = (
        "fused"
        if fused_table_bytes(2 * stats.est_groups, num_accumulators) <= budget
        and backend_refusal("fused") is None
        else None
    )

    if stats.key_domain is not None and stats.key_domain <= 2 * stats.est_groups:
        # direct ticketing: ticket == key, so capacity only needs the domain
        return Plan("direct", "scatter", "dense_psum", table_capacity(stats.key_domain, load_factor=1.0))
    if stats.est_groups <= 4096:
        # Low cardinality: the whole table + accumulators sit in VMEM, the
        # fused kernel's home turf; otherwise MXU one-hot update is
        # contention-free and the matmul is small; dense psum merge is tiny.
        return Plan("hash", "onehot", "dense_psum", cap, fused)
    if unique_frac >= 0.8 and not heavy:
        # Near-unique keys, no skew: ticketing is pure insert; sort-based
        # grouping and a partitioned exchange avoid building a 2× table.
        return Plan("sort", "sort_segment", "all_to_all", cap)
    # General case (the paper's recommended default): concurrent with
    # thread-local/dense merge — resilient to skew at every cardinality.
    return Plan("hash", "scatter", "dense_psum", cap, fused)


class RunningStats:
    """Mergeable workload statistics carried ACROSS stream chunks.

    ``sample_stats`` sees one chunk; a long stream can drift (the heavy-
    hitter mass of a Zipf source only emerges over many chunks, and the
    distinct count grows without bound on near-unique streams).  This
    keeps a tiny host-side sketch updated from a prefix sample of every
    chunk (``update`` reads the sample itself; ``fold`` takes a host copy
    read by the caller):

      * a Misra–Gries counter set (``num_counters`` slots) for heavy-hitter
        mass — deletions decrement all counters, so a surviving counter's
        frequency is a lower bound on the key's true sampled frequency;
      * a bounded union of sampled distinct keys for the cardinality
        estimate (same u-anchored birthday estimator as ``sample_stats``).

    ``strategy="auto"`` executors feed every chunk through ``fold`` and
    re-plan when the observed stats cross a planner threshold (the
    hash→hybrid escalation), and the observed distinct count feeds back
    into capacity bounds.
    """

    def __init__(self, num_counters: int = 16, sample: int = 4096,
                 distinct_cap: int = 1 << 16, domain: int | None = None):
        self.num_counters = num_counters
        self.sample = sample
        self.distinct_cap = distinct_cap
        self.domain = domain
        self.n_rows = 0
        self.sampled = 0
        self._counters: dict[int, int] = {}
        self._distinct: set[int] = set()
        self._distinct_saturated = False

    def update(self, keys: jnp.ndarray) -> "WorkloadStats":
        """Fold one chunk's prefix sample into the sketch; returns the
        refreshed cumulative :class:`WorkloadStats`.  Slices the sample off
        ``keys`` and reads it to the host (blocking) for :meth:`fold`."""
        import numpy as np

        flat = keys.reshape(-1)
        s = min(self.sample, flat.shape[0])
        return self.fold(np.asarray(jax.device_get(flat[:s])), int(flat.shape[0]))

    def fold(self, host_keys, n_rows: int) -> "WorkloadStats":
        """Fold a host copy of one chunk's prefix sample (at most
        ``sample`` keys are read) drawn from ``n_rows`` rows; returns the
        refreshed cumulative :class:`WorkloadStats`.  Host work only, so a
        caller can read the sample off the device whenever it is ready."""
        import numpy as np

        self.n_rows += int(n_rows)
        ks = np.asarray(host_keys).reshape(-1)[: self.sample]
        ks = ks[ks != np.uint32(0xFFFFFFFF)]
        self.sampled += int(ks.size)
        if ks.size:
            uniq, counts = np.unique(ks, return_counts=True)
            for k, c in zip(uniq.tolist(), counts.tolist()):
                if k in self._counters:
                    self._counters[k] += c
                elif len(self._counters) < self.num_counters:
                    self._counters[k] = c
                else:
                    # Weighted Misra–Gries decrement round: pay the smaller
                    # of the newcomer's weight and the lightest counter,
                    # evict the emptied counters, and ADMIT the newcomer
                    # with its residual weight — a heavy hitter must be
                    # able to displace incumbents no matter where its key
                    # id falls in the sample's sorted order.
                    d = min(c, min(self._counters.values()))
                    self._counters = {
                        key: v - d for key, v in self._counters.items() if v > d
                    }
                    if c > d and len(self._counters) < self.num_counters:
                        self._counters[k] = c - d
            if not self._distinct_saturated:
                self._distinct.update(uniq.tolist())
                if len(self._distinct) >= self.distinct_cap:
                    self._distinct_saturated = True
        return self.stats

    @property
    def heavy_keys(self):
        """Current heavy-hitter candidates, heaviest first."""
        return sorted(self._counters, key=self._counters.get, reverse=True)

    def heavy_array(self, limit: int | None = None):
        """Heavy-hitter candidates as a uint32 numpy array, heaviest first —
        the vectorized form routing code (the spill executor's hot-set
        classifier) intersects against whole key columns."""
        import numpy as np

        keys = self.heavy_keys if limit is None else self.heavy_keys[:limit]
        return np.asarray(keys, dtype=np.uint32) if keys else np.zeros((0,), np.uint32)

    @property
    def stats(self) -> WorkloadStats:
        u = len(self._distinct)
        if self.sampled == 0:
            return WorkloadStats(self.n_rows, 1, 0.0, self.domain)
        top = max(self._counters.values(), default=0) / self.sampled
        if self._distinct_saturated or u > 0.5 * self.sampled:
            est = int(min(max(u * self.n_rows / self.sampled, u), self.n_rows))
        else:
            est = u
        return WorkloadStats(self.n_rows, max(est, 1), top, self.domain)


def sample_stats(keys: jnp.ndarray, sample: int = 4096, domain: int | None = None) -> WorkloadStats:
    """Estimate cardinality & skew from a prefix sample (engine fallback when
    no optimizer estimate exists). Uses the birthday-style estimator
    n̂ = u · n / s on the sample's unique count u."""
    flat = keys.reshape(-1)
    s = min(sample, flat.shape[0])
    ks = jax.device_get(flat[:s])
    import numpy as np

    valid = ks[ks != np.uint32(0xFFFFFFFF)]
    if valid.size == 0:
        return WorkloadStats(int(flat.shape[0]), 1, 0.0, domain)
    uniq, counts = np.unique(valid, return_counts=True)
    u = int(uniq.size)
    top = float(counts.max()) / float(valid.size)
    # scale-up: if the sample saw mostly-unique keys, extrapolate linearly;
    # if it saw heavy repetition, the sample cardinality is ≈ the truth
    # (each distinct key recurs within the sample, so unseen keys are rare
    # — anchor the estimate at u instead of inflating it).
    if u > 0.5 * valid.size:
        est = int(min(u * flat.shape[0] / valid.size, flat.shape[0]))
    else:
        est = u
    est = min(max(est, u), int(flat.shape[0]))  # never below u, never above n
    return WorkloadStats(int(flat.shape[0]), est, top, domain)
