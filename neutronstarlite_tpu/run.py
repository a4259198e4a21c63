"""CLI dispatcher: ``python -m neutronstarlite_tpu.run file.cfg``.

Reference: toolkits/main.cpp:34-199 — reads the cfg, loads the graph, and
dispatches on the ALGORITHM string. The reference launches under
``mpiexec -np N`` (run_nts.sh); here distribution comes from the JAX mesh
(all visible devices by default, or PARTITIONS:n in the cfg).
"""

from __future__ import annotations

import os
import sys

from neutronstarlite_tpu.models import get_algorithm
from neutronstarlite_tpu.utils.config import InputInfo
from neutronstarlite_tpu.utils.logging import get_logger

log = get_logger("main")


def apply_launcher_overrides(cfg: InputInfo) -> InputInfo:
    """run_nts.sh parity: its <slots> argument (NTS_PARTITIONS_OVERRIDE)
    overrides the cfg's PARTITIONS — the reference's mpiexec -np N
    (run_nts.sh:2)."""
    slots = os.environ.get("NTS_PARTITIONS_OVERRIDE", "")
    if slots:
        try:
            cfg.partitions = int(slots)
        except ValueError:
            raise SystemExit(
                f"NTS_PARTITIONS_OVERRIDE={slots!r} is not an integer slot "
                "count (run_nts.sh <cfg> <slots> passes it through; unset "
                "it to use the cfg's PARTITIONS)"
            ) from None
        if cfg.partitions < 0:
            raise SystemExit(
                f"NTS_PARTITIONS_OVERRIDE={slots!r} must be >= 0 "
                "(0 = use all devices in the mesh)"
            )
    kern = os.environ.get("NTS_KERNEL_OVERRIDE")
    if kern and kern.strip():
        # launcher parity for the KERNEL: key (the ci_tier1 fused-edge
        # gate runs one smoke cfg through both the eager and fused
        # paths); set-but-empty is NOT an override — the cfg's KERNEL
        # stands, so `NTS_KERNEL_OVERRIDE= ` can't silently reroute a
        # fused benchmark onto the eager chain
        v = kern.strip().lower()
        if v in ("eager", "none"):
            v = ""
        elif v != "fused_edge":
            raise SystemExit(
                f"NTS_KERNEL_OVERRIDE={kern!r} must be fused_edge or "
                "eager/none (unset/empty = the cfg's KERNEL)"
            )
        cfg.kernel = v
    return cfg


def main(argv=None) -> int:
    from neutronstarlite_tpu.parallel.mesh import maybe_initialize_distributed
    from neutronstarlite_tpu.utils.platform import (
        configure_compile_cache,
        start_runtime,
    )

    configure_compile_cache()
    maybe_initialize_distributed()
    argv = argv if argv is not None else sys.argv[1:]
    if len(argv) < 1:
        print("usage: python -m neutronstarlite_tpu.run <config.cfg>", file=sys.stderr)
        return 2
    cfg_path = argv[0]
    cfg = InputInfo.read_from_cfg_file(cfg_path)
    apply_launcher_overrides(cfg)
    print(cfg.print())
    cls = get_algorithm(cfg.algorithm)
    toolkit = cls(cfg, base_dir=os.path.dirname(os.path.abspath(cfg_path)))
    toolkit.init_graph()
    toolkit.init_nn()
    # the sampled trainer has forked its sampler pool by now (it must do so
    # before the first backend touch), so the device can be named
    start_runtime()
    # the supervised wrapper (resilience/): per-epoch health guards +
    # rollback to the last good checkpoint with bounded retries; exits
    # non-zero only when NTS_MAX_RESTARTS is exhausted
    from neutronstarlite_tpu.resilience.supervisor import (
        RetriesExhaustedError,
        supervised_run,
    )

    try:
        result = supervised_run(toolkit)
    except RetriesExhaustedError as e:
        log.error("run failed permanently: %s", e)
        if getattr(toolkit, "run_summary_record", None) is None:
            toolkit.finalize_metrics(None)  # salvage the partial stream
        return 1
    print(toolkit.report())
    log.info("result: %s", result)
    # every run ends with one consolidated run_summary record (obs/);
    # run loops emit it themselves — this covers any trainer that predates
    # the metrics integration
    if getattr(toolkit, "run_summary_record", None) is None and hasattr(
        toolkit, "finalize_metrics"
    ):
        toolkit.finalize_metrics(result if isinstance(result, dict) else None)
    if getattr(toolkit, "metrics", None) is not None and toolkit.metrics.path:
        log.info(
            "run metrics: %s (render with python -m "
            "neutronstarlite_tpu.tools.metrics_report %s)",
            toolkit.metrics.path, toolkit.metrics.path,
        )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
