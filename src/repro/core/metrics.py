"""FL experiment metrics (paper §4.4).

Tracks per-round accuracy/loss/time/bytes and derives:
  * convergence: T_f (first round reaching Acc_t), T_s (round after which
    accuracy stays >= Acc_t), stability T_s − T_f  (§4.4.3, Table 3);
  * oscillation: O_ots — rounds where accuracy drops vs the previous round
    by more than a threshold (§4.4.4, Fig. 3);
  * resource utilization: cumulative transmission bytes per direction,
    simulated training duration, peak resident parameter memory (§4.4.2).

:class:`DeviceMetricsRing` is the device-resident half of the batched
engine's metric path: per-round eval/update-norm scalars are appended as
jitted in-place writes (no ``float()`` host sync in the hot loop) and the
whole ring crosses to the host ONCE when the run flushes.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence

import numpy as np

from repro.obs.profile import record_transfer


class DeviceMetricsRing:
    """Preallocated (capacity, channels) f32 device buffer of per-round
    scalar metrics.

    ``append`` takes *device* scalars (jit outputs: eval accuracy/loss,
    the server round's update norm) and writes them into the next row
    with the buffer donated — one tiny async dispatch, no host transfer,
    so the engine's hot loop never blocks on a metric.  ``flush`` does
    the single device->host copy at run end.

    ``stale_bins`` / ``n_clients`` (the scheduling-stats channels,
    PR 5): when set, the ring additionally owns a device-resident
    staleness histogram (int32 ``(stale_bins,)``, last bin = overflow)
    and per-client participation counts (int32 ``(n_clients,)``).
    ``append_sched`` scatter-adds one aggregation round's (K,) staleness
    and client-index vectors into both with the buffers donated — the
    same no-host-sync discipline as ``append`` — and ``flush_sched``
    does their single device->host copy at run end.

    Unbounded-upload horizons (the streaming channel's queue/timeout
    triggers, PR 6) removed the two fixed-K assumptions the ring was
    built on: ``capacity`` is now a *hint*, not a ceiling — appending
    past it grows the buffer by power-of-two doubling (an explicit
    device reallocation, never a silent overwrite of live rows) — and
    ``append_sched`` accepts any per-round K: inputs are padded host-side
    to the next power of two with out-of-range sentinels the scatter's
    drop mode discards, so the donated writer still compiles O(log K)
    programs instead of one per distinct horizon size.
    """

    def __init__(self, capacity: int, channels: int = 3,
                 stale_bins: int = 0, n_clients: int = 0):
        # lazy import keeps this module importable without jax for
        # host-only consumers of MetricsLog
        import jax.numpy as jnp
        self.capacity = int(capacity)
        self.channels = int(channels)
        # bucket the allocation to a power of two (>= 64): the donated
        # writer program is shape-specialized, so arbitrary capacities
        # would compile one writer per distinct run length
        cap = 1 << (max(64, self.capacity) - 1).bit_length()
        self._buf = jnp.zeros((cap, self.channels), jnp.float32)
        self._n = 0
        self.stale_bins = int(stale_bins)
        self.n_clients = int(n_clients)
        self._hist = self._part = None
        if self.stale_bins:
            self._hist = jnp.zeros((self.stale_bins,), jnp.int32)
            self._part = jnp.zeros((max(self.n_clients, 1),), jnp.int32)

    def append(self, *scalars) -> None:
        assert len(scalars) == self.channels, (len(scalars), self.channels)
        import jax.numpy as jnp
        if self._n >= self._buf.shape[0]:
            # capacity was a hint (timeout horizons can aggregate more
            # rounds than the caller projected): grow by doubling — one
            # explicit O(rows) device copy per doubling, amortized O(1)
            # per append, and the rows already written stay intact
            self._buf = jnp.concatenate(
                [self._buf, jnp.zeros_like(self._buf)])
            self.capacity = self._buf.shape[0]
        self._buf = _ring_write(self._buf, jnp.int32(self._n), *scalars)
        self._n += 1

    def append_sched(self, staleness, cids) -> None:
        """Scatter-add one round's (K,) staleness values and client ids
        (host ints / arrays) into the device histogram / participation
        counts (donated in-place writes, no host transfer).  K may vary
        per round: the vectors are padded to the next power of two with
        out-of-range sentinels (bin index ``stale_bins``, client index
        ``n_clients``) that the writer's drop-mode scatter discards, so
        compilation stays O(log K) under queue/timeout horizons.
        Staleness is clipped into the histogram's overflow bin HERE (host
        side) — in-program clipping would send the sentinels back in
        range."""
        assert self._hist is not None, "ring built without sched channels"
        stal = np.minimum(np.asarray(staleness, np.int32),
                          self.stale_bins - 1)
        ids = np.asarray(cids, np.int32)
        k = stal.shape[0]
        kb = 1 << max(k - 1, 0).bit_length()
        if kb != k:
            stal = np.concatenate(
                [stal, np.full(kb - k, self.stale_bins, np.int32)])
            ids = np.concatenate(
                [ids, np.full(kb - k, self._part.shape[0], np.int32)])
        self._hist, self._part = _sched_write(
            self._hist, self._part, stal, ids)

    def __len__(self) -> int:
        return self._n

    def flush(self) -> np.ndarray:
        """One host transfer: the (n, channels) rows appended so far."""
        record_transfer("metrics_ring.flush")
        return np.asarray(self._buf[:self._n])

    def flush_sched(self):
        """One host transfer: (staleness histogram, participation)."""
        assert self._hist is not None, "ring built without sched channels"
        record_transfer("metrics_ring.flush_sched")
        return (np.asarray(self._hist),
                np.asarray(self._part[:self.n_clients]))


@functools.lru_cache(maxsize=None)
def _ring_writer(channels: int):
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=(0,))
    def write(buf, i, *scalars):
        row = jnp.stack([jnp.asarray(s, jnp.float32) for s in scalars])
        return jax.lax.dynamic_update_slice(buf, row[None], (i, 0))

    return write


def _ring_write(buf, i, *scalars):
    return _ring_writer(len(scalars))(buf, i, *scalars)


@functools.lru_cache(maxsize=None)
def _sched_writer():
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def write(hist, part, staleness, cids):
        # mode="drop": the padding sentinels (index == length) fall out;
        # real staleness was clipped into the overflow bin host-side
        hist = hist.at[staleness].add(1, mode="drop")
        part = part.at[cids].add(1, mode="drop")
        return hist, part

    return write


def _sched_write(hist, part, staleness, cids):
    return _sched_writer()(hist, part, staleness, cids)


@dataclasses.dataclass
class RoundRecord:
    round: int
    sim_time: float
    accuracy: float
    loss: float
    tx_bytes: int  # cumulative client->server
    rx_bytes: int  # cumulative server->client (broadcast)
    mean_staleness: float
    max_staleness: int
    nan_event: bool
    # L2 norm of the applied global-model delta (computed inside the fused
    # server program; 0.0 for paths that don't report it)
    update_norm: float = 0.0
    # CUMULATIVE defense-layer counts at this round (like tx/rx bytes):
    # uploads dropped by the screen and influence-clipped by the norm cap
    screened_uploads: int = 0
    clipped_uploads: int = 0
    # the counters the model reported from this round's evaluation, by
    # name (empty for a model that reports none)
    reported: Dict[str, float] = dataclasses.field(default_factory=dict)


class MetricsLog:
    def __init__(self, target_accuracy: float,
                 oscillation_thresholds: Sequence[float]):
        self.records: List[RoundRecord] = []
        self.target = target_accuracy
        self.ots = tuple(oscillation_thresholds)

    def record(self, **kw) -> None:
        self.records.append(RoundRecord(**kw))

    # ----- §4.4.3 convergence -----
    def t_f(self) -> Optional[int]:
        for r in self.records:
            if r.accuracy >= self.target:
                return r.round
        return None

    def t_s(self) -> Optional[int]:
        """Last round after which accuracy never falls below target."""
        below = [r.round for r in self.records if r.accuracy < self.target]
        if not self.records or self.records[-1].accuracy < self.target:
            return None
        if not below:
            return self.t_f()
        last_below = max(below)
        after = [r.round for r in self.records if r.round > last_below]
        return min(after) if after else None

    def stability(self) -> Optional[int]:
        tf, ts = self.t_f(), self.t_s()
        if tf is None or ts is None:
            return None
        return ts - tf

    # ----- §4.4.4 oscillation -----
    def oscillations(self) -> Dict[float, int]:
        acc = np.array([r.accuracy for r in self.records])
        out = {}
        for th in self.ots:
            drops = acc[:-1] - acc[1:]
            out[th] = int(np.sum(drops > th))
        return out

    # ----- §4.4.1 / §4.4.2 summaries -----
    def best_accuracy(self) -> float:
        return max((r.accuracy for r in self.records), default=0.0)

    def final_accuracy(self) -> float:
        return self.records[-1].accuracy if self.records else 0.0

    def total_tx_bytes(self) -> int:
        return self.records[-1].tx_bytes if self.records else 0

    def total_rx_bytes(self) -> int:
        return self.records[-1].rx_bytes if self.records else 0

    def duration(self) -> float:
        return self.records[-1].sim_time if self.records else 0.0

    def nan_rounds(self) -> int:
        return sum(1 for r in self.records if r.nan_event)

    def first_nan_round(self) -> Optional[int]:
        for r in self.records:
            if r.nan_event:
                return r.round
        return None

    def screened_uploads(self) -> int:
        return self.records[-1].screened_uploads if self.records else 0

    def clipped_uploads(self) -> int:
        return self.records[-1].clipped_uploads if self.records else 0

    def accuracy_curve(self) -> np.ndarray:
        return np.array([(r.round, r.accuracy) for r in self.records])

    def summary(self) -> Dict:
        return {
            "rounds": len(self.records),
            "best_accuracy": self.best_accuracy(),
            "final_accuracy": self.final_accuracy(),
            "T_f": self.t_f(),
            "T_s": self.t_s(),
            "stability": self.stability(),
            "oscillations": self.oscillations(),
            "nan_rounds": self.nan_rounds(),
            "screened_uploads": self.screened_uploads(),
            "clipped_uploads": self.clipped_uploads(),
            "duration_s": self.duration(),
            "tx_GB": self.total_tx_bytes() / 1e9,
            "rx_GB": self.total_rx_bytes() / 1e9,
            "mean_staleness": float(np.mean(
                [r.mean_staleness for r in self.records])) if self.records
            else 0.0,
        }
