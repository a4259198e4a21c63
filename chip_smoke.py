"""Standing check that the system starts and runs on the chip.

    python3 chip_smoke.py

One process drives the main paths once through the entry points a user
calls (``neutronstarlite_tpu.run.main`` and ``serve.server.main``), at the
full width of the Reddit-shaped GCN (602-128-41 on V=232,965), with the
dataset and the weights made from seeds. It needs a TPU: on any other
backend it prints what JAX reported and exits 2 without a result. On the
chip it exits 0 only when every leg passed. The last line of stdout is
``{"ok": ..., "device": {"platform", "kind", "count"}}`` with exactly those
keys; the line before it, ``chip_smoke report: {...}``, carries the versions,
the compile cache and each leg's status, times and losses, and is also
written to ``chiprun_out/chip_smoke.json``.

Legs (the first failure exits non-zero):

0. device gate: ``jax.default_backend() == "tpu"``, judged by what JAX
   reports and never by the environment;
1. dataset from a seed (``graph.prep.prepare("reddit")`` at its defaults);
2. ``train_fullbatch``: the settings of configs/gcn_reddit_full.cfg, 5 epochs;
3. ``train_pallas``: the same plus ``PALLAS:1``; the step must hold a Mosaic
   custom call (the bsp kernel compiled, not interpreted) and track leg 2;
4. ``train_sampled_then_serve``: GCNSAMPLESINGLE with the fused on-device
   epoch scan, 2 epochs, checkpointed; then the server answers 64 requests
   from that checkpoint in this same process;
5. ``train_seqlm``: the token-sequence family (ALGORITHM:SEQLM) on the
   published widths of configs/moonlight_16b_a3b.json at a small cut (the
   dense layer and one expert layer, 8 of 64 experts, an eighth of the
   vocabulary, two sequences of 1,024 tokens a step), 4 steps on one batch;
6. ``dist4``, on four or more devices only: GCNDIST over four partitions at
   the default exchange and at ``DIST_PATH:ring_blocked``, one process
   driving the four chips.

``dist4`` runs straight after the dataset, before legs 2-4: its placement
check reads each device's peak bytes, a counter JAX keeps for the life of
the process, and legs 2-4 fill device 0. Its losses are compared with
leg 2's once leg 2 has run.

Every time printed here is a set-up fact of this run (how long a cold or
warm start takes), not a performance metric.
"""

from __future__ import annotations

import faulthandler
import gc
import glob
import importlib.metadata
import json
import math
import multiprocessing
import os
import shutil
import sys
import tempfile
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
PLATFORM = "tpu"  # what JAX must report; a constant, not an option
DEADLINE_S = 1150  # the driver allows 1200 s; dump stacks and exit before it

# Loss trajectories of two runs of the same model on the same data. Legs 2
# and 3 share the math and the key stream and differ in the aggregation
# kernel's bf16 rounding; a dist run also draws its dropout masks per shard
# over the padded vertex layout. Both are small against a loss of ln(41).
PALLAS_LOSS_TOL = 0.05
DIST_LOSS_TOL = 0.10
# no device may hold more than this multiple of the median device's peak
PLACEMENT_SKEW = 2.0


class LegFailed(Exception):
    pass


def require(cond: bool, what: str) -> None:
    if not cond:
        raise LegFailed(what)


def device_gate() -> dict:
    """Leg 0. Returns the device facts; exits 2 off the TPU."""
    import jax
    import jaxlib

    devices = jax.devices()
    facts = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    versions = {
        "jax": jax.__version__, "jaxlib": jaxlib.__version__, "libtpu": libtpu,
    }
    print(
        f"chip_smoke device: platform={facts['platform']} "
        f"device_kind={facts['kind']!r} count={facts['count']} "
        f"jax={versions['jax']} jaxlib={versions['jaxlib']} libtpu={libtpu}",
        flush=True,
    )
    if jax.default_backend() != PLATFORM:
        print(
            f"chip_smoke: needs the {PLATFORM!r} backend, JAX reports "
            f"{jax.default_backend()!r}; nothing was run",
            file=sys.stderr, flush=True,
        )
        sys.exit(2)
    return {"device": facts, "versions": versions}


def result_line(summary: dict) -> str:
    """The last line of stdout, which the driver reads: ``ok`` and the
    device as JAX reported it, and no other key."""
    device = summary["device"]
    return json.dumps({
        "ok": bool(summary["ok"]),
        "device": {
            "platform": device["platform"], "kind": device["kind"],
            "count": device["count"],
        },
    })


def cache_entries(cache_dir: str) -> int:
    return sum(len(files) for _, _, files in os.walk(cache_dir))


# ---- cfg files -------------------------------------------------------------


def base_settings() -> dict:
    """KEY -> VALUE of configs/gcn_reddit_full.cfg, the one source of the
    workload's settings."""
    settings = {}
    with open(os.path.join(REPO, "configs", "gcn_reddit_full.cfg")) as fh:
        for line in fh:
            line = line.strip()
            if line and not line.startswith("#"):
                key, _, value = line.partition(":")
                settings[key] = value
    return settings


def write_cfg(path: str, data: dict, **overrides) -> str:
    settings = base_settings()
    settings.update(
        EDGE_FILE=data["edge_file"], FEATURE_FILE=data["feature_file"],
        LABEL_FILE=data["label_file"], MASK_FILE=data["mask_file"],
        VERTICES=data["v_num"],
    )
    settings.update(overrides)
    with open(path, "w") as fh:
        for key, value in settings.items():
            if value is not None:
                fh.write(f"{key}:{value}\n")
    return path


# ---- reading a leg's metrics stream ----------------------------------------


def read_events(metrics_dir: str) -> list:
    events = []
    for path in sorted(glob.glob(os.path.join(metrics_dir, "*.jsonl"))):
        with open(path) as fh:
            events.extend(json.loads(line) for line in fh if line.strip())
    return events


def check_training(events: list, epochs: int) -> dict:
    """The pass criteria every training leg shares; returns its summary."""
    faults = [e for e in events if e["event"] in ("fault", "recovery")]
    require(not faults, f"fault/recovery records in a clean run: {faults[:3]}")
    summaries = [e for e in events if e["event"] == "run_summary"]
    require(len(summaries) == 1, f"{len(summaries)} run_summary records")
    summ = summaries[0]
    losses = summ["loss_history"]
    require(len(losses) == epochs, f"{len(losses)} epochs ran, want {epochs}")
    require(all(math.isfinite(v) for v in losses), f"non-finite loss {losses}")
    require(losses[-1] < losses[0], f"loss did not fall: {losses}")
    require(summ["device"]["platform"] == PLATFORM, f"ran on {summ['device']}")
    mem = summ["memory"]
    require(
        mem["available"] and (mem["peak_bytes_in_use"] or 0) > 0,
        f"device memory stats unavailable: {mem}",
    )
    return summ


def leg_report(summ: dict, t0: float) -> dict:
    return {
        "status": "passed",
        "wall_s": round(time.time() - t0, 1),
        "first_compile_s": round(summ["epoch_time"]["compile_overhead_s"], 2),
        "losses": [round(v, 4) for v in summ["loss_history"]],
        "peak_bytes": summ["memory"]["peak_bytes_in_use"],
    }


def require_losses_close(got: list, want: list, tol: float, what: str) -> float:
    diff = max(abs(a - b) for a, b in zip(got, want))
    require(
        diff <= tol,
        f"{what}: losses {got} are {diff:.4f} from {want[:len(got)]}, "
        f"over the tolerance {tol}",
    )
    return round(diff, 4)


def run_training(cfg_path: str, metrics_dir: str, epochs: int) -> tuple:
    from neutronstarlite_tpu.run import main as run_main

    os.environ["NTS_METRICS_DIR"] = metrics_dir
    rc = run_main([cfg_path])
    gc.collect()  # drop the trainer's device arrays before the next leg
    require(rc == 0, f"run.main exited {rc}")
    events = read_events(metrics_dir)
    return events, check_training(events, epochs)


# ---- legs ------------------------------------------------------------------


def leg_train_fullbatch(root: str, data: dict) -> tuple:
    t0 = time.time()
    cfg = write_cfg(os.path.join(root, "fullbatch.cfg"), data, EPOCHS=5)
    _, summ = run_training(cfg, os.path.join(root, "m_fullbatch"), 5)
    return leg_report(summ, t0), summ["loss_history"]


def leg_train_pallas(root: str, data: dict, want_losses: list) -> dict:
    t0 = time.time()
    cfg = write_cfg(os.path.join(root, "pallas.cfg"), data, EPOCHS=5, PALLAS=1)
    events, summ = run_training(cfg, os.path.join(root, "m_pallas"), 5)
    steps = [
        e for e in events
        if e["event"] == "program_cost"
        and e["label"].startswith("fullbatch.train_step/")
    ]
    require(len(steps) == 1, f"{len(steps)} train-step program_cost records")
    require(
        "tpu_custom_call" in (steps[0].get("custom_calls") or []),
        "no Mosaic custom call in the lowered train step "
        f"(custom calls: {steps[0].get('custom_calls')}): the bsp kernel "
        "did not lower for the chip",
    )
    report = leg_report(summ, t0)
    report["custom_calls"] = steps[0]["custom_calls"]
    report["max_loss_diff_vs_fullbatch"] = require_losses_close(
        summ["loss_history"], want_losses, PALLAS_LOSS_TOL, "train_pallas"
    )
    return report


def leg_train_sampled_then_serve(root: str, data: dict) -> dict:
    from neutronstarlite_tpu.serve.server import main as serve_main

    t0 = time.time()
    cfg = write_cfg(
        os.path.join(root, "sampled.cfg"), data,
        ALGORITHM="GCNSAMPLESINGLE", OPTIM_KERNEL=None, EPOCHS=2,
        FANOUT="25-10", BATCH_SIZE=512, SAMPLE_PIPELINE="fused",
        CHECKPOINT_DIR=os.path.join(root, "ckpt"), CHECKPOINT_EVERY=1,
    )
    _, summ = run_training(cfg, os.path.join(root, "m_sampled"), 2)
    report = leg_report(summ, t0)

    t_serve = time.time()
    serve_dir = os.path.join(root, "m_serve")
    os.environ["NTS_METRICS_DIR"] = serve_dir
    rc = serve_main([cfg, "--requests", "64"])
    gc.collect()
    require(rc == 0, f"serve.server.main exited {rc} (request errors?)")
    served = [e for e in read_events(serve_dir) if e["event"] == "serve_summary"]
    require(len(served) == 1, f"{len(served)} serve_summary records")
    s = served[0]
    require(s["requests"] == 64, f"served {s['requests']} of 64")
    require(s["shed"] == 0, f"shed {s['shed']} requests")
    counts = s["compile_counts"]
    require(
        counts and all(v == 1 for v in counts.values()),
        f"a bucket compiled other than once: {counts}",
    )
    report.update(
        wall_s=round(time.time() - t0, 1),
        serve={
            "wall_s": round(time.time() - t_serve, 1),
            "requests": s["requests"], "shed": s["shed"], "errors": 0,
            "compile_counts": counts,
        },
    )
    return report


def leg_train_seqlm(root: str) -> dict:
    t0 = time.time()
    settings = {
        "ALGORITHM": "SEQLM",
        "MODEL_FILE": os.path.join(REPO, "configs", "moonlight_16b_a3b.json"),
        "SEQ_LAYERS": 2, "EXPERT_SHARDS": 8, "EXPERT_SHARD": 0, "VOCAB_SHARDS": 8,
        "SEQ_LENGTH": 1024, "SEQ_BATCH": 2, "SEQ_CORPUS": 1, "EPOCHS": 4,
        "PRECISION": "bfloat16", "LEARN_RATE": 0.0003, "DECAY_EPOCH": -1,
    }
    cfg = os.path.join(root, "seqlm.cfg")
    with open(cfg, "w") as fh:
        fh.writelines(f"{k}:{v}\n" for k, v in settings.items())
    _, summ = run_training(cfg, os.path.join(root, "m_seqlm"), 4)
    counters = summ.get("counters") or {}
    report = leg_report(summ, t0)
    report["rows_routed"] = counters.get("moe.rows_routed")
    require(counters.get("seq.tokens") == 4 * 2 * 1024, f"seq.tokens {counters.get('seq.tokens')}")
    require((counters.get("moe.rows_routed") or 0) > 0, "no pair was routed to a held expert")
    return report


def leg_dist4(root: str, data: dict) -> dict:
    """Both exchanges on four partitions; the comparison with leg 2's
    losses happens in main() once leg 2 has run."""
    report = {}
    for name, overrides in (
        ("all_gather", {}),
        ("ring_blocked", {"DIST_PATH": "ring_blocked"}),
    ):
        t0 = time.time()
        cfg = write_cfg(
            os.path.join(root, f"dist4_{name}.cfg"), data,
            ALGORITHM="GCNDIST", PARTITIONS=4, EPOCHS=3, **overrides,
        )
        _, summ = run_training(cfg, os.path.join(root, f"m_dist4_{name}"), 3)
        # peaks are kept for the life of the process: the second run's
        # include the first's, which is balanced or has failed already
        peaks = [d["peak_bytes_in_use"] or 0 for d in summ["memory"]["devices"]]
        require(len(peaks) >= 4, f"{name}: memory stats for {len(peaks)} devices")
        mesh_peaks = sorted(peaks, reverse=True)[:4]
        median = (mesh_peaks[1] + mesh_peaks[2]) / 2
        require(min(mesh_peaks) > 0, f"{name}: a mesh device held nothing: {peaks}")
        require(
            mesh_peaks[0] <= PLACEMENT_SKEW * median,
            f"{name}: one device peaked at {mesh_peaks[0]} bytes, over "
            f"{PLACEMENT_SKEW}x the median {median:.0f} (per device: {peaks}) "
            "— an array landed whole on one device",
        )
        report[name] = dict(leg_report(summ, t0), device_peak_bytes=peaks)
    report["status"] = "passed"
    return report


# ---- main ------------------------------------------------------------------


def main() -> int:
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)
    t_start = time.time()
    summary = {"ok": False, **device_gate()}

    # a retried compile or a rolled-back NaN must not pass as a clean run
    os.environ["NTS_MAX_RESTARTS"] = "0"
    from neutronstarlite_tpu import native
    from neutronstarlite_tpu.graph import prep
    from neutronstarlite_tpu.utils.platform import configure_compile_cache

    cache_dir = configure_compile_cache()
    summary["compile_cache"] = {
        "dir": cache_dir, "entries_before": cache_entries(cache_dir),
    }
    summary["graph_builder"] = native.builder_name()
    print(
        f"chip_smoke: compile cache {cache_dir} "
        f"({summary['compile_cache']['entries_before']} entries), host graph "
        f"builder {summary['graph_builder']}",
        flush=True,
    )

    # the 660 MB dataset, cfgs, checkpoints and metrics streams: outside the
    # checkout and chiprun_out/ (never copied back), removed on the way out
    root = tempfile.mkdtemp(prefix="nts_chip_smoke_")
    legs = summary["legs"] = {}
    leg = "dataset"
    try:
        t0 = time.time()
        data = prep.prepare("reddit", root)
        legs[leg] = {
            "status": "passed", "wall_s": round(time.time() - t0, 1),
            "v_num": data["v_num"], "e_num": data["e_num"],
        }

        n_dev = summary["device"]["count"]
        dist = None
        if n_dev >= 4:
            leg = "dist4"
            dist = leg_dist4(root, data)

        leg = "train_fullbatch"
        legs[leg], full_losses = leg_train_fullbatch(root, data)
        leg = "train_pallas"
        legs[leg] = leg_train_pallas(root, data, full_losses)
        leg = "train_sampled_then_serve"
        legs[leg] = leg_train_sampled_then_serve(root, data)
        leg = "train_seqlm"
        legs[leg] = leg_train_seqlm(root)

        leg = "dist4"
        if dist is None:
            legs[leg] = f"not_run: {n_dev} device(s)"
        else:
            for name in ("all_gather", "ring_blocked"):
                dist[name]["max_loss_diff_vs_fullbatch"] = require_losses_close(
                    dist[name]["losses"], full_losses, DIST_LOSS_TOL,
                    f"dist4 {name}",
                )
            legs[leg] = dist
        summary["ok"] = True
    except LegFailed as e:
        legs[leg] = {"status": "failed", "error": str(e)}
        print(f"chip_smoke: leg {leg} FAILED: {e}", file=sys.stderr, flush=True)
    except Exception:  # a leg crashed: report which one, with the traceback
        legs[leg] = {"status": "failed", "error": traceback.format_exc()[-4000:]}
        print(f"chip_smoke: leg {leg} CRASHED", file=sys.stderr, flush=True)
        traceback.print_exc()
    finally:
        shutil.rmtree(root, ignore_errors=True)

    for child in multiprocessing.active_children():
        child.terminate()  # nothing started here may outlive the script
    summary["compile_cache"]["entries_after"] = cache_entries(cache_dir)
    summary["wall_s"] = round(time.time() - t_start, 1)
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "chip_smoke.json"), "w") as fh:
        json.dump(summary, fh, indent=1)
    faulthandler.cancel_dump_traceback_later()
    print("chip_smoke report: " + json.dumps(summary), flush=True)
    print(result_line(summary), flush=True)  # nothing may follow it on stdout
    return 0 if summary["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
