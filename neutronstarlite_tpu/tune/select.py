"""Auto-knob resolution: the hook the lifecycle funnel and the elastic
replan consult.

``resolve_auto_knobs(toolkit)`` runs at the top of
``ToolkitBase._finalize_datum`` — BEFORE the funnel's validity checks and
before ``build_model`` — and replaces every ``auto`` cfg axis
(DIST_PATH / KERNEL / ELL_LEVELS / WIRE_DTYPE) with a concrete value:

- ``NTS_TUNE=off`` (the default): ``DIST_PATH:auto`` keeps its
  pre-tuner legacy meaning (defer to the COMM_LAYER heuristic —
  existing cfgs keep parsing AND behaving unchanged); any OTHER auto
  axis refuses loudly — a knob the tuner alone can resolve must not
  silently degrade to a default while the user benchmarks it as tuned.
- ``NTS_TUNE=cached``: consult the persisted cache
  (tune/cache.py). Hit -> apply the cached decision, zero trials. Miss
  -> decide from the analytic prior alone (deterministic, no device
  work, NOT persisted — a later ``measure`` run must still measure).
- ``NTS_TUNE=measure``: hit -> as cached; miss -> enumerate the funnel-
  valid space, prior-prune, run the timed micro-trials
  (tune/runner.py), pick the best measured score, and atomically
  persist the decision.

Either way one typed ``tune_decision`` record lands in the obs stream
(candidate, source = measured | cached | prior, score) and the ``tune.*``
gauges pin the choice for metrics_report / run_summary consumers. The
funnel's own ``_check_*`` validity gates still run AFTER resolution on
the concrete values, so even a buggy cache entry cannot smuggle in a
combination the funnel refuses — it dies at the same loud gate a
hand-written cfg would.

``reconsult_for_replan(toolkit)`` is the elastic integration
(resilience/elastic.replan_survivors): after a rank loss shrinks the
plan to P' = P − 1, the knobs that were resolved by the tuner are
re-resolved for P' — a cached P' entry is a hit; otherwise the analytic
prior decides (``decision_source=prior``). Measurements NEVER run inside
the recovery path: the cluster is degraded and the supervisor is
mid-rollback; trials there would stretch time-to-recover for a decision
the next ``measure`` run can refine.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional, Set

from neutronstarlite_tpu.tune import cache, runner, space
from neutronstarlite_tpu.utils.logging import get_logger

log = get_logger("tune")


def _simulate_active(toolkit) -> bool:
    """Whether the trainer will run the collective-free sim twin (the
    ToolkitBase.resolve_mesh rule + the explicit _sim spelling)."""
    sim = getattr(toolkit, "simulate", None)
    if sim is not None:
        return bool(sim)
    if getattr(toolkit.cfg, "dist_path", "") == "ring_blocked_sim":
        return True
    return os.environ.get("NTS_DIST_SIMULATE", "0") == "1"


def _partition_count(toolkit) -> int:
    """The P the decision is keyed by — the trainer's DEVICE budget: a
    concrete MESH:Pv,Pf pins it at Pv*Pf, else cfg PARTITIONS, else all
    visible devices (sim default 2, the resolve_mesh fallback); 1 for
    single-chip families. MESH:auto enumerates the factorizations of
    this same budget, so the decision stays keyed by one number."""
    fam = space.family_of(type(toolkit))
    if fam not in ("dist_dense", "edge_dist"):
        return 1
    mesh_v = space._norm("mesh", getattr(toolkit.cfg, "mesh", ""))
    if mesh_v not in ("", "auto"):
        from neutronstarlite_tpu.parallel.partitioner import MeshSpec

        return MeshSpec.parse(mesh_v).devices
    cfg_p = int(getattr(toolkit.cfg, "partitions", 0) or 0)
    if cfg_p:
        return cfg_p
    if _simulate_active(toolkit):
        return 2
    import jax

    return len(jax.devices())


def _graph_digest_of(toolkit) -> str:
    digest = getattr(toolkit, "_tune_graph_digest", None)
    if digest is None:
        from neutronstarlite_tpu.graph.digest import graph_digest

        digest = graph_digest(toolkit.host_graph)
        toolkit._tune_graph_digest = digest
    return digest


def _cache_key(toolkit, family: str, P: int) -> cache.CacheKey:
    return cache.CacheKey(
        graph_digest=_graph_digest_of(toolkit),
        family=family,
        partitions=int(P),
        layers=toolkit.cfg.layer_string,
        backend=cache.backend_fingerprint(),
    )


def _decision_matches_pins(decision: Dict[str, Any], cfg,
                           autos: Set[str]) -> bool:
    """A cached decision is only reusable when its pinned-axis values
    still match the cfg — a user re-pinning an axis after the entry was
    measured makes the joint decision stale (warned miss, re-tune)."""
    for axis in space.AXES:
        if axis in autos:
            continue
        if space._norm(axis, decision.get(axis, "")) != space._norm(
            axis, getattr(cfg, axis, "")
        ):
            return False
    return True


def _apply(toolkit, decision: Dict[str, Any], autos: Set[str]) -> None:
    for axis in autos:
        setattr(toolkit.cfg, axis, decision.get(axis, ""))


def _emit_decision(toolkit, family: str, P: int,
                   decision: Dict[str, Any], source: str) -> None:
    metrics = getattr(toolkit, "metrics", None)
    if metrics is None:
        return
    # the decision record carries the FULL cache-key facts like the
    # trial records do (digest/backend/layers as open fields): the drift
    # auditor's numerics leg (tools/drift_audit.wire_quant_drift) must be
    # able to flag exactly the implicated entry from a CACHED-mode stream
    # too, which has zero tune_trial records to borrow the key from
    key = _cache_key(toolkit, family, P)
    metrics.event(
        "tune_decision",
        family=family,
        candidate=decision["candidate"],
        source=source,
        partitions=int(P),
        seconds=decision.get("seconds"),
        predicted_bytes=decision.get("predicted_bytes"),
        decision={a: decision.get(a, "") for a in space.AXES},
        graph_digest=key.graph_digest,
        backend=key.backend,
        layers=key.layers,
    )
    metrics.gauge_set("tune.decision", decision["candidate"])
    metrics.gauge_set("tune.decision_source", source)
    metrics.gauge_set("tune.partitions", int(P))


def _decide(toolkit, autos: Set[str], measure_allowed: bool,
            in_recovery: bool) -> None:
    """Resolve ``autos`` through cache -> trials -> prior and apply."""
    cfg = toolkit.cfg
    cls = type(toolkit)
    family = f"{space.family_of(cls)}/{cls.__name__}"
    P = _partition_count(toolkit)
    key = _cache_key(toolkit, family, P)

    entry = cache.load(key)
    if entry is not None and entry.get("drift_flag") and measure_allowed \
            and not in_recovery:
        # the drift auditor (tools/drift_audit.py) marked this entry's
        # cost model wrong: in measure mode that is a loud miss — re-run
        # real trials (the fresh store replaces the entry, clearing the
        # flag). Cached mode and the recovery path still replay below
        # (measuring there is worse than a degraded decision).
        log.warning(
            "tune cache: entry %s is drift-flagged (%s) — re-trialing "
            "instead of replaying a decision whose cost model drifted",
            key.filename(), (entry["drift_flag"] or {}).get("reason"),
        )
        entry = None
    if entry is not None:
        if entry.get("drift_flag"):
            log.warning(
                "tune cache: replaying drift-flagged entry %s (%s) — run "
                "with NTS_TUNE=measure to re-trial it",
                key.filename(), (entry["drift_flag"] or {}).get("reason"),
            )
        decision = entry["decision"]
        stored_autos = set(entry.get("autos") or [])
        if not autos <= stored_autos:
            # the user freed an axis the entry never explored (e.g. the
            # entry was measured with WIRE_DTYPE pinned and wire is auto
            # now): replaying it would silently skip the comparison the
            # auto spelling asks for — re-tune instead
            log.warning(
                "tune cache: entry %s was measured with auto axes %s but "
                "%s are auto now — the entry never explored the newly "
                "freed axis; re-tuning",
                key.filename(), sorted(stored_autos), sorted(autos),
            )
        elif _decision_matches_pins(decision, cfg, autos):
            _apply(toolkit, decision, autos)
            _emit_decision(toolkit, family, P, decision, source="cached")
            log.info(
                "tune: cached decision %s (P=%d, %s)",
                decision["candidate"], P, key.filename(),
            )
            return
        else:
            log.warning(
                "tune cache: entry %s was decided under different pinned "
                "axes — re-tuning", key.filename(),
            )

    sim = _simulate_active(toolkit)
    fam_short = space.family_of(cls)
    candidates = space.enumerate_candidates(cls, cfg, P, simulate=sim)
    if not candidates:
        raise ValueError(
            f"tune: no funnel-valid candidate exists for ALGORITHM "
            f"{cfg.algorithm!r} with the pinned axes "
            f"{ {a: getattr(cfg, a) for a in space.AXES if a not in autos} }"
            " — relax a pin or drop the auto knobs"
        )
    sizes = cfg.layer_sizes()
    C = 1
    if fam_short in ("edge_single", "edge_dist") and len(sizes) > 1:
        chan = getattr(cls, "edge_score_channels", None)
        if chan is not None:
            C = int(chan(sizes[1]))
    sample_cfg = None
    if fam_short == "sampled":
        # the sampled-family legs measure at the model's REAL shape
        # (batch size + per-layer fan-outs) and the prior prices the real
        # per-epoch payload, so both need the trainer's sampling facts
        import numpy as np

        fans = cfg.fanouts()
        if len(sizes) > 1 and fans:
            fans = fans[-(len(sizes) - 1):]
        datum = getattr(toolkit, "datum", None)
        mask = getattr(datum, "mask", None) if datum is not None else None
        n_seeds = (
            int((np.asarray(mask) == 0).sum()) if mask is not None
            else int(toolkit.host_graph.v_num) // 3
        )
        sample_cfg = {
            "batch_size": int(cfg.batch_size or 16),
            "fanouts": fans,
            "n_seeds": n_seeds,
        }
    metrics = getattr(toolkit, "metrics", None)
    # trial records carry the FULL cache-key facts (digest/backend/
    # layers ride as open fields), so the drift auditor can flag exactly
    # the implicated entry instead of every (family, P) entry across
    # graphs and rigs
    key_ctx = {
        "graph_digest": key.graph_digest,
        "backend": key.backend,
        "layers": key.layers,
    }
    emit = (
        (lambda kind, **f: metrics.event(kind, **dict(key_ctx, **f)))
        if metrics is not None else None
    )
    measure = measure_allowed and not in_recovery
    rows = runner.score_candidates(
        toolkit.host_graph, P, sizes, fam_short, candidates,
        simulate=sim, emit=emit, measure=measure, family_label=family,
        metrics=metrics,
        kernel_tile=cfg.kernel_tile, edge_chunk=cfg.edge_chunk,
        score_channels=C, precision=cfg.precision,
        eager_widths=bool(getattr(cls, "eager", False)),
        input_hoisted=toolkit.hoists_input_aggregate(),
        sample_cfg=sample_cfg,
    )
    if metrics is not None and measure:
        metrics.counter_add(
            "tune.trials", sum(1 for r in rows if r["seconds"] is not None)
        )
    best = runner.pick_best(rows)
    by_label = {c.label(): c for c in candidates}
    chosen = by_label[best["candidate"]]
    decision = dict(chosen.as_dict(), **best)
    source = "measured" if best["seconds"] is not None else "prior"
    _apply(toolkit, decision, autos)
    _emit_decision(toolkit, family, P, decision, source=source)
    log.info(
        "tune: %s decision %s (P=%d, score=%s, predicted=%dB, %d "
        "candidates)",
        source, decision["candidate"], P,
        f"{best['seconds'] * 1e3:.3f}ms" if best["seconds"] is not None
        else "n/a",
        best["predicted_bytes"], len(candidates),
    )
    if source == "measured":
        # only measured decisions persist: a prior-only resolution must
        # not stop a later NTS_TUNE=measure run from actually measuring
        cache.store(key, decision, trials=rows, autos=sorted(autos))
    elif measure_allowed:
        log.warning(
            "tune: nothing was measurable on this rig; decided from the "
            "analytic prior (decision not persisted)"
        )


# ---- public entry points ----------------------------------------------------


def resolve_auto_knobs(toolkit) -> None:
    """Resolve every ``auto`` cfg axis before the funnel's validity
    checks (called from ToolkitBase._finalize_datum). No-op when nothing
    is auto."""
    cfg = toolkit.cfg
    # NTS_MESH launcher parity folds in HERE — the head of the funnel —
    # so the env spelling flows through the same auto-resolution and
    # validity checks the cfg key gets (parallel/partitioner.py)
    from neutronstarlite_tpu.parallel.partitioner import fold_mesh_env

    fold_mesh_env(cfg)
    autos = space.auto_axes(cfg)
    if not autos:
        return
    mode = cache.tune_mode()
    if mode == "off":
        others = autos - {"dist_path"}
        if others:
            raise ValueError(
                f"{', '.join(sorted(a.upper() for a in others))}:auto "
                "requested but the autotuner is off (NTS_TUNE=off): set "
                "NTS_TUNE=cached or NTS_TUNE=measure (and NTS_TUNE_DIR "
                "for persistence), or pin a concrete value — silently "
                "running a default while the cfg says auto is the "
                "mis-benchmark the lifecycle funnel exists to refuse"
            )
        # DIST_PATH:auto predates the tuner: without NTS_TUNE it keeps
        # its legacy meaning (defer to the COMM_LAYER heuristic)
        return
    toolkit._tune_autos = set(autos)
    _decide(toolkit, autos, measure_allowed=(mode == "measure"),
            in_recovery=False)


def reconsult_for_replan(toolkit) -> bool:
    """Re-resolve the tuner-owned knobs for the survivor plan (called by
    elastic.replan_survivors AFTER cfg.partitions was shrunk to P',
    BEFORE build_model). Cache hit for P' -> cached decision; miss ->
    analytic prior (``decision_source=prior``); measurements never run
    here. Returns True when a re-resolution happened."""
    autos = getattr(toolkit, "_tune_autos", None)
    if not autos:
        return False
    # restore the auto markers so enumeration sees the original freedom
    for axis in autos:
        setattr(toolkit.cfg, axis, "auto")
    _decide(toolkit, set(autos), measure_allowed=False, in_recovery=True)
    return True
