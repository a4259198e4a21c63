"""The set-up's readers (``harness/setup_spans.py`` and the seven metrics
over it) on hand-made span records: a warm run, a cold run, a run whose
check compiled after the window, a ring without the spans. And end to end
in a CPU rehearsal of one GCN cell and of the token-sequence cell."""

import json
import os
import subprocess
import sys
import types

import pytest

from harness import program_spans, setup_spans, spec

SPAN_METRICS = ("runtime_start_s", "setup_compile_s", "setup_cache_misses",
                "first_step_trace_s", "first_step_backend_s", "setup_unspanned_s")
T_PROCESS, WINDOW = 100.2, (140.0, 160.0)  # the process began at 100.0 by the kernel


def span(span_id, name, cat, t0, dur_s, parent_id="run", **attrs):
    return dict(attrs, event="span", span_id=span_id, name=name, cat=cat, t0=t0, dur_s=dur_s,
                parent_id=parent_id)


def compile_span(span_id, parent_id, end, fun, trace_s, lower_s, backend_s, cache,
                 retrieve_s=0.0):
    dur_s = trace_s + lower_s + backend_s
    return span(span_id, "compile", "compile", end - dur_s, dur_s, parent_id, fun=fun,
                trace_s=trace_s, lower_s=lower_s, backend_s=backend_s, retrieve_s=retrieve_s,
                cache=cache)


def a_run(cache, step_backend_s, with_setup_spans=True, check_compile=False):
    """A run's records: the prelude to 111.0, a constructor from 113.0 with
    two phases and a gap of 1.0 between them, the warm-up ``run()`` from
    121.0 to 135.0 (two epochs, the step compiled under the first one's
    dispatch), then 5.0 under no span (the benchmark's copy of the weights)
    to the window's start. ``cache`` and ``step_backend_s`` are the step's."""
    retrieve_s = 0.6 * step_backend_s if cache == "hit" else 0.0
    records = [
        span("p0", "params_init", "phase", 113.0, 3.0),
        span("p1", "datum_upload", "phase", 117.0, 2.0),  # 116.0-117.0: a gap
        span("g0", "run_begin", "stage", 121.0, 0.5),
        span("d0", "step_dispatch", "stage", 121.5, 10.5, "e0"),
        span("w0", "step_device", "stage", 132.0, 1.0, "e0"),
        span("e0", "epoch", "epoch", 121.5, 11.5),
        span("d1", "step_dispatch", "stage", 133.0, 0.1, "e1"),
        span("w1", "step_device", "stage", 133.1, 0.9, "e1"),
        span("e1", "epoch", "epoch", 133.0, 1.0),
        span("f0", "finalize_metrics", "stage", 134.0, 1.0),
        span("run", "run", "lifecycle", 112.5, 22.5, None),
        span("e2", "epoch", "epoch", 140.0, 1.0),  # the window's first epoch
    ]
    if with_setup_spans:
        records += [
            span("s0", "process_prelude", "startup", 100.0, 11.0, None, backend_live=1),
            compile_span("c0", "p0", 115.0, "jit(init_params)", 0.25, 0.25, 1.0, cache,
                         0.5 if cache == "hit" else 0.0),
            compile_span("c1", "d0", 131.5, "jit(_step)", 2.0, 1.0, step_backend_s, cache,
                         retrieve_s),
        ]
    if check_compile:  # the program's scope table, after the window, under a span
        records.append(span("t0", "scope_table", "stage", 161.0, 4.0))
        records.append(compile_span("c2", "t0", 164.0, "jit(_step)", 0.0, 0.0, 2.5, "hit", 2.0))
    return records


@pytest.fixture
def read(monkeypatch):
    """``read(records, metric)``: a metric's reader over hand-made records."""
    ctx = types.SimpleNamespace(t_process_start=T_PROCESS, spans={"datum_s": 1.5})

    def go(records, metric):
        monkeypatch.setattr(program_spans, "span_records", lambda: records)
        return spec.layer_reader(metric)(ctx, {"window": WINDOW})

    return go


def test_a_warm_run(read):
    records = a_run("hit", 6.0)
    assert read(records, "runtime_start_s") == 11.0
    assert read(records, "setup_compile_s") == pytest.approx(1.5 + 9.0)
    assert read(records, "setup_cache_misses") == 0.0
    assert read(records, "first_step_trace_s") == pytest.approx(3.0)
    assert read(records, "first_step_backend_s") == pytest.approx(6.0)
    # 39.8 s of set-up; covered: the prelude from the stamp (10.8), the
    # phases (5.0), the run loop's stages and epochs (121.0-135.0); not
    # covered: 111-113, 116-117, 119-121, 135-140 = 10.0, less the datum's 1.5
    assert read(records, "setup_unspanned_s") == pytest.approx(8.5)


def test_a_cold_run_says_which_programs_missed(read):
    records = a_run("miss", 9.0)
    assert read(records, "setup_cache_misses") == 2.0
    assert read(records, "first_step_backend_s") == pytest.approx(9.0)
    assert read(records, "setup_compile_s") == pytest.approx(1.5 + 12.0)


def test_what_compiles_after_the_window_is_no_part_of_set_up(read):
    warm, late = a_run("hit", 6.0), a_run("hit", 6.0, check_compile=True)
    for metric in SPAN_METRICS:
        assert read(late, metric) == pytest.approx(read(warm, metric)), metric


def test_a_ring_without_the_spans_reads_as_none(read):
    for records in (a_run("hit", 6.0, with_setup_spans=False), [], None):
        for metric in SPAN_METRICS:
            assert read(records, metric) is None, metric


def test_the_split_names_each_stretch_by_the_span_before_it():
    split = dict(setup_spans.setup_by_span(a_run("hit", 6.0), T_PROCESS, WINDOW[0]))
    assert split["process_prelude"] == pytest.approx(10.8)  # clipped to the stamp
    assert split["no span after process_prelude"] == pytest.approx(2.0)
    assert split["no span after params_init"] == pytest.approx(1.0)
    assert split["no span after datum_upload"] == pytest.approx(2.0)
    assert split["no span after finalize_metrics"] == pytest.approx(5.0)
    # a span's own time is its interval less its children: the step's
    # compile (9.0 s) comes off its dispatch, the init's off its phase
    assert split["compile jit(_step)"] == pytest.approx(9.0)
    assert split["compile jit(init_params)"] == pytest.approx(1.5)
    assert split["step_dispatch"] == pytest.approx(10.6 - 9.0)
    assert split["params_init"] == pytest.approx(3.0 - 1.5)
    assert "run" not in split and "epoch" not in split  # an epoch is all stages here
    assert sum(split.values()) == pytest.approx(WINDOW[0] - T_PROCESS)


def test_the_step_program_reader_reads_the_gauge_or_nothing(monkeypatch):
    reader = spec.layer_reader("step_program_mb")
    monkeypatch.setattr(setup_spans, "gauge", lambda name: {"step.generated_code_bytes": 345e6}.get(name))
    assert reader(None, {}) == pytest.approx(345.0)
    monkeypatch.setattr(setup_spans, "gauge", lambda name: None)
    assert reader(None, {}) is None


@pytest.mark.parametrize("workload, metrics", [
    ("gcn_reddit_full.train", SPAN_METRICS),
    ("moonlight_16b_a3b_ep8.train", SPAN_METRICS + ("step_program_mb",)),
])
def test_a_rehearsal_would_report_the_new_metrics(workload, metrics):
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false")
    done = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"), "--workload", workload,
         "--seed", "3000000019", "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=spec.REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["correct"] and line["compiles_in_window"] == 0
    assert set(metrics) <= set(line["would_report"])
    assert "setup by span [[" in done.stderr and "setup compiles " in done.stderr
