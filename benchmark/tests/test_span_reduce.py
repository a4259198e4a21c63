"""The program's ``nts:`` spans against the device's busy time
(``harness/span_reduce.py``) on hand-made host lines and busy intervals,
the record readers (``harness/program_spans.py``) on hand-made span
records, and the new readers end to end in a CPU rehearsal.
"""

import json
import os
import subprocess
import sys

import pytest

from conftest import FIXTURES
from harness import program_spans, span_reduce as sr, spec, trace_reduce as tr

TRACE_METRICS = ("epoch_host_tail_ms", "step_launch_ms_max", "steady_idle_share",
                 "idle_unspanned_share")
RECORD_METRICS = ("first_step_s", "final_eval_s", "tables_build_s", "datum_upload_s",
                  "funnel_unspanned_s")
STAGES = (  # name, offset of its start in the epoch, length
    ("epoch_key", 0.0, 0.02), ("step_dispatch", 0.02, 0.08), ("step_device", 0.1, 8.9),
    ("loss_fetch", 9.0, 0.2),
    ("epoch_emit", 9.2, 0.2), ("logits_copy", 9.4, 0.4), ("host_accuracy", 9.8, 0.15),
)


def epoch_events(start, whole=True):
    """One epoch's events: its stages from ``start`` and, for a whole
    epoch, the ``nts:epoch`` event that holds them, 10 s long with 0.05 s
    of its own at the end."""
    events = [(start + a, start + a + n, "nts:" + name) for name, a, n in STAGES]
    if whole:
        events.append((start, start + 10.0, "nts:epoch"))
    return events


def reduction(busy, host_events, window=(0.0, 29.5)):
    device = tr.DeviceReduction(ordinal=0, busy=tr.clip(tr.union(busy), *window),
                                by_group={}, collective=[], collective_exposed_s=0.0)
    return tr.Reduction(window=window, devices=[device],
                        host_lines={"python": sorted(host_events),
                                    "other": [(0.0, 1.0, "tpu::System::Execute")]})


def three_epochs(first_launch=0.05):
    """A window of three epochs, the last cut by the profiler's stop (no
    ``nts:epoch`` event, no stage after its loss fetch), under ``nts:run``
    (opened before the window). The device runs each step from ``launch``
    after the dispatch opens to 9 s into the epoch."""
    host = [(-5.0, 40.0, "nts:run"), (-1.0, 35.0, "benchmark_window")]
    host += epoch_events(0.0) + epoch_events(10.0) + epoch_events(20.0, whole=False)[:4]
    # (0.015, 0.03): the first epoch's key, still running when its dispatch opens
    busy = [(0.015, 0.03), (first_launch, 9.0), (10.05, 19.0), (20.05, 29.0)]
    return reduction(busy, host)


def test_a_held_first_launch_goes_to_its_span_and_not_to_the_steady_share():
    red = three_epochs(first_launch=6.0)
    assert [e["epoch"] is not None for e in sr.window_epochs(red)] == [True, True, False]
    assert sr.step_launch_ms_max(red) == pytest.approx(5980.0)  # dispatch opens at 0.02
    split = dict(sr.idle_by_span(red))
    assert split["e0/nts:step_device"] == pytest.approx(5.9)
    assert split["e0/nts:step_dispatch"] == pytest.approx(0.07)  # 0.03 to 0.1
    # the cut epoch's key, ahead of its dispatch, is that epoch's
    assert split["e2/nts:epoch_key"] == pytest.approx(0.02)
    assert "nts:epoch_key" not in split
    assert sum(split.values()) == pytest.approx(red.window_s - red.busy_s)
    # from the second epoch's start (10.0) to the window's end: 2 x 1.05 s
    # idle around the steps, 0.5 s after the last one
    assert sr.steady_idle_share(red) == pytest.approx(100.0 * 1.6 / 19.5)
    held = sr.steady_idle_share(red)
    assert sr.steady_idle_share(three_epochs()) == pytest.approx(held)
    assert sr.step_launch_ms_max(three_epochs()) == pytest.approx(30.0)


def test_epoch_host_tail_is_the_epoch_less_dispatch_and_device_wait():
    red = three_epochs()
    assert sr.epoch_host_tail_ms(red) == pytest.approx(1020.0)  # two whole epochs of 10 - 8.98
    only_cut = reduction([(0.05, 9.0)], epoch_events(0.0, whole=False), window=(0.0, 9.5))
    assert sr.epoch_host_tail_ms(only_cut) is None
    assert sr.steady_idle_share(only_cut) is None  # one epoch: no second to start from
    assert sr.step_launch_ms_max(only_cut) == pytest.approx(30.0)


def test_idle_under_no_stage_span_is_unspanned():
    red = three_epochs()
    split = dict(sr.idle_by_span(red))
    # each whole epoch keeps 0.05 s of its own after host_accuracy; the cut
    # epoch's last 0.3 s lie under nts:run alone
    assert split["e0/nts:epoch"] == pytest.approx(0.05)
    assert split["e1/nts:epoch"] == pytest.approx(0.05)
    assert split["nts:run"] == pytest.approx(0.3)
    idle = red.window_s - red.busy_s
    assert sr.idle_unspanned_share(sr.idle_by_span(red)) == pytest.approx(100.0 * 0.4 / idle)
    # the same trace without the root: that time is under no event at all
    bare = reduction(red.devices[0].busy,
                     [e for e in red.host_lines["python"] if e[2] != "nts:run"])
    assert dict(sr.idle_by_span(bare))[sr.NO_SPAN] == pytest.approx(0.3)
    assert sr.idle_unspanned_share(sr.idle_by_span(bare)) == pytest.approx(100.0 * 0.4 / idle)
    # a gap that straddles two stages is shared by intersection
    assert split["e0/nts:loss_fetch"] == pytest.approx(0.2)
    assert split["e0/nts:logits_copy"] == pytest.approx(0.4)


def test_a_busy_device_at_dispatch_is_no_launch_and_no_idle():
    host = [(-5.0, 40.0, "nts:run")] + epoch_events(0.0) + epoch_events(10.0)
    red = reduction([(-1.0, 20.0)], host, window=(0.0, 20.0))
    assert sr.step_launch_ms_max(red) == 0.0
    assert sr.idle_by_span(red) == [] and sr.idle_unspanned_share([]) == 0.0
    assert sr.steady_idle_share(red) == pytest.approx(0.0)


@pytest.mark.parametrize("fixture, window", [
    ("v5e_1chip.xplane.pb", "bench_step"), ("v5e_4chip.xplane.pb", "benchmark_window"),
])
def test_a_trace_without_nts_events_reads_as_none(fixture, window):
    """An older program still runs the benchmark: every trace reader
    returns None, and raises nothing."""
    red = tr.reduce_file(os.path.join(FIXTURES, fixture), window)
    assert sr.loop_events(red) == [] and sr.window_epochs(red) == []
    ctx = type("Ctx", (), {"reduction": red, "spans": {}})()
    for name in TRACE_METRICS:
        assert spec.layer_reader(name)(ctx, {}) is None
    ctx.reduction = None  # an untraced run, a rehearsal
    for name in TRACE_METRICS:
        assert spec.layer_reader(name)(ctx, {}) is None


def span(name, cat, t0, dur, span_id, parent="s0"):
    return {"event": "span", "name": name, "cat": cat, "t0": t0, "dur_s": dur,
            "span_id": span_id, "parent_id": parent}


def test_record_readers_on_hand_made_records(monkeypatch):
    records = [
        span("tune_resolve", "phase", 0.0, 0.5, "s1"),
        span("tables_build", "phase", 1.0, 3.0, "s2"),
        span("datum_upload", "phase", 4.0, 0.25, "s3"),
        span("datum_upload", "phase", 4.5, 0.5, "s5", parent="s4"),  # inside step_build
        span("step_build", "phase", 4.25, 1.0, "s4"),
        span("epoch", "epoch", 6.0, 7.0, "s6"),
        span("datum_upload", "phase", 6.1, 2.0, "s7", parent="s6"),  # lazy, in the first step
        span("epoch", "epoch", 13.0, 2.9, "s8"),
        span("final_eval", "stage", 16.0, 4.0, "s9"),
        span("run", "lifecycle", -1.0, 21.0, "s0", parent=None),
        span("final_eval", "stage", 40.0, 3.5, "s10"),  # the window's run()
    ]
    monkeypatch.setattr(program_spans, "span_records", lambda: records)
    ctx = type("Ctx", (), {"reduction": None, "spans": {"trainer_build_s": 5.5}})()
    got = {name: spec.layer_reader(name)(ctx, {}) for name in RECORD_METRICS}
    assert got == {
        "first_step_s": 7.0, "final_eval_s": 4.0, "tables_build_s": 3.0,
        "datum_upload_s": 2.75, "funnel_unspanned_s": pytest.approx(5.5 - 4.75),
    }
    # a program without the ring (an older commit, NTS_FLIGHT=0): nothing
    monkeypatch.setattr(program_spans, "span_records", lambda: None)
    assert all(spec.layer_reader(name)(ctx, {}) is None for name in RECORD_METRICS)


def test_every_new_metric_has_its_entry_and_its_reader():
    bench = spec.load_benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in TRACE_METRICS + RECORD_METRICS:
        assert callable(spec.layer_reader(name))
        cells = ["gcn_reddit_full.train"] + (
            [] if name == "final_eval_s" else ["gcn_products_dist4.train"])
        assert entries[name]["workloads"] == cells
        assert entries[name]["moves"] == (
            "setup_s" if name in RECORD_METRICS else "epoch_s")


@pytest.mark.parametrize("workload, absent", [
    ("gcn_reddit_full.train", ()), ("gcn_products_dist4.train", ("final_eval_s",)),
])
def test_rehearsal_would_report_the_record_metrics(workload, absent):
    """End to end on the CPU: the program's funnel and run loops emit the
    spans, the ring keeps them past the trainer, the readers find them. The
    trace readers report nothing there: a rehearsal has no device plane."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false")
    done = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"), "--workload", workload,
         "--seed", "3000000019", "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=spec.REPO, env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        timeout=600, check=True,
    )
    line = json.loads(done.stdout.decode().strip().splitlines()[-1])
    assert line["rehearsal"] and line["correct"]
    would = set(line["would_report"])
    assert set(RECORD_METRICS) - set(absent) <= would
    assert not would & (set(TRACE_METRICS) | set(absent))
