"""Elastic degraded-mode training: rank-health tracking + survivor replan.

NeutronStar's MPI lineage dies whole-job on any rank failure — one lost
``mpiexec`` slot aborts the communicator and the training run with it.
This module is the elastic spine that keeps a partitioned run alive
through a partition loss instead:

- **Rank-health tracking** — :class:`LivenessMonitor` consumes one
  heartbeat per partition per epoch (each beat is also a typed
  ``heartbeat`` record in the obs stream), and raises
  :class:`RankLossError` (``HealthError`` with ``code=rank_loss``) when a
  partition misses ``NTS_HEARTBEAT_MISS_K`` consecutive beats or a
  collective step exceeds ``NTS_COLLECTIVE_TIMEOUT_S``. Detection emits a
  typed ``rank_loss`` record naming the partition and reason before the
  raise, so the loss is reconstructable from telemetry alone.
- **Chaos integration** — the ``rank_loss@partition=k`` fault kind
  (resilience/faults) kills one *sim* partition mid-epoch by registering
  it here (:func:`kill_partition`); the trainer's per-epoch heartbeat
  emission then skips the dead partition, and the monitor detects the
  loss exactly the way a real missing rank's silence would surface.
  The dead set is process-global on purpose (like the fault plan): a
  supervised retry inside the same process must still see the partition
  as dead until a replan renumbers the survivors.
- **Survivor replan** — :func:`replan_survivors` rebuilds the
  distributed plan for P' = P − 1 at the rollback boundary: the host
  graph is re-range-partitioned over the survivors
  (parallel/dist_graph + vertex_space — the lost partition's vertex
  range is redistributed, boundaries rebalance), ``build_model``
  re-derives the ring skip schedule / blocks / padded vertex arrays /
  jitted step for P', and a typed ``replan`` record (old/new P, lost
  partition, redistributed-vertex count, rebuild seconds) lands in the
  stream. Params and optimizer state are partition-INDEPENDENT
  (replicated), so the supervisor then restores them from the last-good
  checkpoint over the rebuilt plan and training continues degraded.

- **Straggler advisory (slow vs dead)** — the straggler detector
  (obs/skew) notes slow-but-alive partitions here
  (:func:`note_straggler`); the registry never sheds or raises — it only
  annotates a LATER rank_loss on the same partition ("flagged slow
  before it went silent"). A straggler is NOT a rank_loss:
  docs/RESILIENCE.md has the contract.

The supervisor (resilience/supervisor) owns the recovery decision: on a
:class:`RankLossError` with an identified partition it replans instead
of retrying the same plan; a collective-timeout detection with no
identified partition falls back to the ordinary same-plan rollback.

Sim-vs-collective caveat: the liveness/replan control plane is exercised
end to end on the collective-free sim twin (``DIST_PATH:
ring_blocked_sim`` — what tier-1 runs on the CPU rig), where one process
simulates every partition. On a real multi-process mesh the JAX runtime
cannot today evict a device from a live mesh: replan re-shards over the
first P' *visible* devices, so surviving a genuine hardware loss
additionally needs the launcher to restart the JAX runtime without the
dead host — the plan rebuild, checkpoint restore, and telemetry here are
exactly the pieces that restart reuses (docs/RESILIENCE.md).
"""

from __future__ import annotations

import os
import time
from typing import Iterable, List, Optional, Set

from neutronstarlite_tpu.resilience import events, guards
from neutronstarlite_tpu.utils.logging import get_logger

log = get_logger("elastic")


class RankLossError(guards.HealthError):
    """A partition stopped participating; ``partition`` names it (None
    for a collective-timeout detection, which cannot attribute)."""

    code = "rank_loss"

    def __init__(self, msg: str, partition: Optional[int] = None,
                 epoch: Optional[int] = None):
        super().__init__(msg, epoch=epoch)
        self.partition = partition


# ---- knobs ------------------------------------------------------------------


def elastic_enabled() -> bool:
    """``NTS_ELASTIC=1`` arms elastic degraded mode (liveness heartbeats
    + survivor replan on rank loss). Off by default: heartbeat records
    and the monitor cost nothing when unarmed."""
    return os.environ.get("NTS_ELASTIC", "0") == "1"


def heartbeat_miss_k() -> int:
    """Consecutive missed beats before a partition is declared lost
    (``NTS_HEARTBEAT_MISS_K``, default 3, clamped to >= 1 — a zero or
    negative K would declare every partition dead on the spot)."""
    raw = os.environ.get("NTS_HEARTBEAT_MISS_K", "")
    try:
        return max(int(raw), 1) if raw else 3
    except ValueError:
        log.warning("bad NTS_HEARTBEAT_MISS_K=%r; using 3", raw)
        return 3


def collective_timeout_s() -> float:
    """Per-step collective budget (``NTS_COLLECTIVE_TIMEOUT_S``, default
    0 = off, negative values clamp to off)."""
    raw = os.environ.get("NTS_COLLECTIVE_TIMEOUT_S", "")
    try:
        return max(float(raw), 0.0) if raw else 0.0
    except ValueError:
        log.warning("bad NTS_COLLECTIVE_TIMEOUT_S=%r; disabling", raw)
        return 0.0


# ---- process-global dead-partition registry (chaos integration) -------------

_dead: Set[int] = set()
# partitions evicted by replans, in ORIGINAL launch numbering — fault
# specs are written against the original plan, so a spec firing AFTER a
# replan must translate its id onto the renumbered survivors (original
# rank 3 is current index 2 once rank 0 is gone)
_lost_originals: List[int] = []


def current_index_of(original: int) -> Optional[int]:
    """The current (post-replan) index of a partition named in ORIGINAL
    launch numbering; None when that partition was already evicted."""
    if original in _lost_originals:
        return None
    return original - sum(1 for l in _lost_originals if l < original)


def _original_index_of(current: int) -> int:
    """Inverse of :func:`current_index_of` over the survivors."""
    o = 0
    seen = 0
    while True:
        if o not in _lost_originals:
            if seen == current:
                return o
            seen += 1
        o += 1


def kill_partition(partition: int) -> None:
    """Mark a sim partition dead (the ``rank_loss`` fault kind's effect):
    its heartbeats stop from the next epoch on. ``partition`` is in
    ORIGINAL launch numbering; a spec that fires after a replan kills
    the same physical rank under its new index, and one naming an
    already-evicted rank is ignored (it cannot die twice)."""
    cur = current_index_of(int(partition))
    if cur is None:
        log.warning(
            "rank_loss: partition %d was already evicted by an earlier "
            "replan; ignoring", partition,
        )
        return
    _dead.add(cur)


def dead_partitions() -> Set[int]:
    return set(_dead)


# ---- advisory straggler registry (slow vs dead, obs/skew) -------------------

# partitions the straggler detector (obs/skew.StragglerDetector) flagged
# slow-but-alive, in CURRENT numbering. ADVISORY ONLY: nothing here
# sheds a partition or raises — a straggler still completes epochs and
# still heartbeats. The registry exists so a LATER rank_loss on a
# known-slow partition can say "it was flagged slow first" (the _trip
# message below), turning slow-then-dead into one readable story.
_stragglers: Set[int] = set()


def note_straggler(partition: int) -> None:
    """The detector's ``on_straggler`` hook (models/gcn_dist wires it)."""
    _stragglers.add(int(partition))


def clear_straggler(partition: int) -> None:
    _stragglers.discard(int(partition))


def stragglers() -> Set[int]:
    return set(_stragglers)


def alive_partitions(partitions: int) -> List[int]:
    """The partitions of a P-way plan still beating (run loops pass this
    to :meth:`LivenessMonitor.epoch_end` each epoch). A dead mark
    OUTSIDE the plan (``rank_loss@partition=7`` on a 4-partition run)
    refuses loudly — it would otherwise never be reported missing and
    the chaos test would pass vacuously, the 'spec that silently never
    fires' failure mode the fault-spec loudness contract forbids."""
    ghost = sorted(p for p in _dead if p >= partitions or p < 0)
    if ghost:
        raise ValueError(
            f"rank_loss fault names partition(s) {ghost} but the plan "
            f"has only {partitions} (0..{partitions - 1}): the injected "
            "loss would silently never be detected"
        )
    return [p for p in range(partitions) if p not in _dead]


def reset() -> None:
    """Forget every killed partition and the replan renumber history
    (tests; ``supervised_run`` calls this on exit so injected deaths
    never leak into the next run in the process)."""
    _dead.clear()
    _lost_originals.clear()
    _stragglers.clear()


def renumber_after_loss(lost: int) -> None:
    """Remap the dead set onto the survivors' new 0..P'-1 numbering
    after a replan drops ``lost`` (a CURRENT index): the lost partition
    leaves the set, survivors above it shift down one, and the eviction
    is recorded in original numbering so later-firing fault specs keep
    naming the right physical rank. A SECOND partition that died before
    the first loss was detected must stay dead under the new numbering —
    clearing the set would silently resurrect it and its planted loss
    would never fire, exactly the chaos-test failure mode the
    fault-spec loudness contract exists to prevent. Its heartbeats keep
    missing on the degraded plan, so it is detected (and replanned
    away) next."""
    global _dead
    _lost_originals.append(_original_index_of(int(lost)))
    _dead = {p - 1 if p > lost else p for p in _dead if p != lost}


# ---- liveness monitor -------------------------------------------------------


class LivenessMonitor:
    """Per-partition heartbeat bookkeeping for one training attempt.

    The trainer constructs one per ``run()`` attempt and calls
    :meth:`epoch_end` once per epoch with the partitions that beat; the
    monitor emits one typed ``heartbeat`` record per live partition,
    counts consecutive misses per partition, and trips (``rank_loss``
    record + :class:`RankLossError`) at ``miss_k`` misses or when the
    epoch's collective step time exceeds ``collective_timeout_s`` (the
    attempt's first epoch is exempt — it pays compile/restore, the same
    exemption the StallError guard grants). A partition that beats again
    before K resets its miss count (transient network wobble is not a
    rank loss). Like every guard, the monitor only *raises* when the
    guards are armed (supervised run / ``NTS_GUARDS=1``); unarmed it
    logs and keeps the stream records flowing."""

    def __init__(self, partitions: int, miss_k: Optional[int] = None,
                 collective_timeout: Optional[float] = None):
        self.partitions = int(partitions)
        self.miss_k = miss_k if miss_k is not None else heartbeat_miss_k()
        self.miss_k = max(int(self.miss_k), 1)
        t = (collective_timeout if collective_timeout is not None
             else collective_timeout_s())
        self.collective_timeout_s = max(float(t), 0.0)
        self._missed = {p: 0 for p in range(self.partitions)}
        self._epochs_seen = 0
        self._tripped: Set[int] = set()  # unarmed: one record per loss

    def epoch_end(self, epoch: int, alive: Optional[Iterable[int]] = None,
                  step_seconds: Optional[float] = None,
                  partition_seconds: Optional[dict] = None) -> None:
        """One epoch's health gate: beats for ``alive`` partitions, miss
        accounting for the rest, and the collective-timeout check.
        ``partition_seconds`` ({partition: measured epoch wall time})
        rides each beat as the optional ``seconds`` field — the raw
        material of the offline straggler replay (obs/skew)."""
        live = set(alive) if alive is not None else set(range(self.partitions))
        secs = partition_seconds or {}
        for p in sorted(live):
            self._missed[p] = 0
            s = secs.get(p)
            events.emit(
                "heartbeat", partition=int(p), epoch=int(epoch),
                **({"seconds": float(s)} if s is not None else {}),
            )
        self._epochs_seen += 1
        for p in range(self.partitions):
            if p in live:
                continue
            self._missed[p] += 1
            if self._missed[p] >= self.miss_k:
                self._trip(
                    f"partition {p} missed {self._missed[p]} consecutive "
                    f"heartbeat(s) (NTS_HEARTBEAT_MISS_K={self.miss_k})",
                    partition=p, epoch=epoch, reason="heartbeat_miss",
                    missed=self._missed[p],
                )
        if (
            self.collective_timeout_s > 0
            and self._epochs_seen > 1  # first epoch pays compile/restore
            and step_seconds is not None
            and step_seconds > self.collective_timeout_s
        ):
            self._trip(
                f"collective step took {step_seconds:.3f}s "
                f"(> NTS_COLLECTIVE_TIMEOUT_S={self.collective_timeout_s:g}s"
                ") — a wedged exchange reads as a lost rank",
                partition=None, epoch=epoch, reason="collective_timeout",
            )

    def missed(self, partition: int) -> int:
        """Consecutive missed beats for one partition — the serve fleet's
        monitor consumes this directly (its guards are never armed, so
        detection cannot rely on the RankLossError raise)."""
        return self._missed.get(int(partition), 0)

    def clear(self, partition: int) -> None:
        """Forget a partition's miss count and trip latch — called after
        a supervised replica restart (serve/fleet.py): the fresh replica
        is a new liveness subject, and a SECOND death must re-detect
        (and re-record) rather than being swallowed by the latch."""
        self._missed[int(partition)] = 0
        self._tripped.discard(int(partition))

    def _trip(self, msg: str, partition: Optional[int], epoch: int,
              reason: str, missed: Optional[int] = None) -> None:
        if partition is not None and partition in _stragglers:
            # the slow-then-dead story: the straggler advisory flagged
            # this partition before its heartbeats stopped
            msg += (f" — partition {partition} was flagged as a straggler "
                    "(slow) before it went silent")
        key = -1 if partition is None else partition
        if key not in self._tripped:
            self._tripped.add(key)
            events.emit(
                "rank_loss",
                partition=int(partition) if partition is not None else None,
                epoch=int(epoch), reason=reason,
                **({"missed_beats": int(missed)} if missed is not None
                   else {}),
            )
        if not guards.guards_armed():
            log.warning(
                "rank loss detected but guards are unarmed: %s (wrap with "
                "resilience.supervised_run + NTS_ELASTIC=1 to replan)", msg,
            )
            return
        raise RankLossError(msg, partition=partition, epoch=epoch)


# ---- survivor replan --------------------------------------------------------


def replan_survivors(toolkit, lost_partition: int) -> int:
    """Rebuild ``toolkit``'s distributed plan for the survivors.

    1D plan: re-range-partition the host graph over P' = P − 1 (the lost
    partition's vertex range is redistributed and every boundary
    rebalances — the ``moved_vertices`` count in the replan record
    quantifies it). 2D plan (a MESH:Pv,Pf partitioner,
    parallel/partitioner.py): the replan is a MESH RESHAPE — losing a
    device shrinks the budget to Pv*Pf − 1 and the best (Pv', Pf') is
    re-emitted for that count: a tuner-owned mesh (MESH:auto) re-consults
    the decision cache through ``reconsult_for_replan`` (warm P' entry =
    cached replay; cold = analytic prior — never a measurement
    mid-recovery), while a pinned mesh falls back to the analytic
    ``choose_mesh_shape`` (the pinned shape cannot exist on fewer
    devices — a loudly-logged forced reshape). Either way
    ``build_model()`` re-derives the DistGraph / ring skip schedule /
    slab layout / padded vertex arrays / jitted step, and the replan
    record carries ``from_mesh``/``to_mesh`` next to the partition
    counts. Params are NOT touched here — they are partition-
    independent, and the supervisor restores them from the last-good
    checkpoint over the rebuilt plan. Returns the new vertex-partition
    count.

    2D caveat: a mesh reshape renumbers EVERY vertex partition (Pv' is
    not generally Pv − 1), so the chaos dead-set translation
    (:func:`renumber_after_loss`) is exact only for the 1D path; a
    second pre-registered sim death keeps missing heartbeats on the
    reshaped plan and is re-detected there."""
    from neutronstarlite_tpu.parallel.vertex_space import reassigned_vertices

    spec = getattr(toolkit, "mesh_spec", None)
    dist = getattr(toolkit, "dist", None)
    old_p = dist.partitions if dist is not None else (
        toolkit.cfg.partitions or 2
    )
    old_total = spec.devices if spec is not None else old_p
    new_total = old_total - 1
    if new_total < 1:
        raise ValueError(
            f"cannot replan a {old_total}-device plan: no survivors"
        )
    old_offsets = dist.offsets.copy() if dist is not None else None
    t0 = time.perf_counter()
    toolkit.cfg.partitions = new_total
    if spec is not None:
        autos = getattr(toolkit, "_tune_autos", None) or set()
        # a tuner-owned shape needs nothing here: reconsult_for_replan
        # below restores every _tune_autos axis (mesh included) to
        # "auto" and re-enumerates the shrunk budget's factorizations
        # (cache hit for P' or analytic prior)
        if "mesh" not in autos:
            from neutronstarlite_tpu.models.gcn_dist import exchange_widths
            from neutronstarlite_tpu.parallel.partitioner import (
                choose_mesh_shape,
            )

            sizes = toolkit.cfg.layer_sizes()
            if len(sizes) > 1:
                widths = exchange_widths(
                    getattr(type(toolkit), "eager", False), sizes,
                    toolkit.hoists_input_aggregate(),
                ) or [sizes[0]]
                outs = sizes[1:]
            else:
                widths = sizes or [1]
                outs = None
            new_spec = choose_mesh_shape(
                toolkit.host_graph, new_total, widths, out_widths=outs
            )
            toolkit.cfg.mesh = new_spec.cfg_value()
            log.warning(
                "mesh reshape: pinned MESH:%s cannot survive on %d "
                "devices; analytic reshape -> MESH:%s",
                spec.label(), new_total, new_spec.label(),
            )
    # survivors renumber to 0..P'-1; a partition that ALSO died before
    # this detection stays dead under the new numbering and is detected
    # (and replanned away) on the retry
    renumber_after_loss(int(lost_partition))
    # a trainer whose knobs were tuner-resolved (DIST_PATH:auto / MESH:
    # auto etc., tune/select) re-consults the decision cache for the
    # survivor count BEFORE the plan rebuilds: a cached entry is a hit,
    # otherwise the analytic prior decides (decision_source=prior in the
    # tune_decision record) — the recovery path never runs measurements,
    # a degraded cluster mid-rollback is the wrong place to benchmark
    from neutronstarlite_tpu.tune import select as tune_select

    tune_select.reconsult_for_replan(toolkit)
    toolkit.build_model()
    seconds = time.perf_counter() - t0
    new_dist = getattr(toolkit, "dist", None)
    new_p = new_dist.partitions if new_dist is not None else new_total
    new_spec_built = getattr(toolkit, "mesh_spec", None)
    moved = None
    if old_offsets is not None and new_dist is not None:
        moved = reassigned_vertices(old_offsets, new_dist.offsets)
    mesh_fields = {}
    if spec is not None:
        mesh_fields["from_mesh"] = spec.label()
        mesh_fields["to_mesh"] = (
            new_spec_built.label() if new_spec_built is not None
            else f"{new_p}x1"
        )
    events.emit(
        "replan",
        from_partitions=int(old_p), to_partitions=int(new_p),
        lost=int(lost_partition), seconds=float(seconds),
        **({"moved_vertices": int(moved)} if moved is not None else {}),
        **mesh_fields,
    )
    log.warning(
        "survivor replan: %d -> %d partitions%s (lost partition %d, %s "
        "vertices re-owned, plan rebuilt in %.2fs); restoring params from "
        "the last-good checkpoint",
        old_p, new_p,
        (f" (mesh {mesh_fields['from_mesh']} -> {mesh_fields['to_mesh']})"
         if mesh_fields else ""),
        lost_partition,
        moved if moved is not None else "?", seconds,
    )
    return new_p
