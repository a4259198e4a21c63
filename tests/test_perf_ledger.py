"""Perf ledger (obs/ledger) + regression sentinel (tools/perf_sentinel).

The ISSUE 13 acceptance pair lives here: a seeded synthetic ledger whose
±10% noise does NOT trip the sentinel, and an injected 25% warm-epoch
regression that DOES (exit 2) — the MAD-scaled trend baseline doing what
the pairwise --diff gate could not on a rig with 20% run-to-run swing.
"""

from __future__ import annotations

import json
import os

import pytest

from neutronstarlite_tpu.obs import ledger
from neutronstarlite_tpu.tools import perf_sentinel

# deterministic ±10%-band noise multipliers (median 1.0, MAD 0.04): the
# rig-noise stand-in every sentinel scenario below shares
NOISE = (1.00, 0.96, 1.04, 1.08, 0.92)


def _run_row(warm_s, wire=1000, **over):
    row = {
        "kind": "run", "ts": 0.0, "run_id": "r", "algorithm": "GCNCPU",
        "cfg": "cfgfp", "graph_digest": "digest", "backend": "cpu-test",
        "epochs": 2, "warm_median_epoch_s": warm_s,
        "wire_bytes_fwd_per_epoch": wire,
    }
    row.update(over)
    return row


def _seeded(directory, base=0.1):
    for mult in NOISE:
        ledger.append_row(_run_row(base * mult), directory=directory)


# ---- ledger mechanics -------------------------------------------------------


def test_append_read_roundtrip_and_schema_stamp(tmp_path):
    d = str(tmp_path)
    path = ledger.append_row(_run_row(0.1), directory=d)
    assert path == os.path.join(d, ledger.LEDGER_FILENAME)
    rows = ledger.read_rows(directory=d)
    assert len(rows) == 1
    assert rows[0]["ledger_schema"] == ledger.LEDGER_SCHEMA_VERSION
    assert rows[0]["warm_median_epoch_s"] == 0.1
    assert ledger.row_key(rows[0]) == ("run", "digest", "cfgfp", "cpu-test")


def test_disabled_without_dir(monkeypatch):
    monkeypatch.delenv("NTS_LEDGER_DIR", raising=False)
    assert ledger.append_row(_run_row(0.1)) is None
    assert ledger.read_rows() == []


def test_torn_line_is_skipped_not_fatal(tmp_path):
    d = str(tmp_path)
    _seeded(d)
    path = os.path.join(d, ledger.LEDGER_FILENAME)
    with open(path, "a") as fh:
        fh.write('{"kind": "run", "warm_median_epo')  # torn final line
    rows = ledger.read_rows(directory=d)
    assert len(rows) == len(NOISE)
    # appends carry prior lines over as raw bytes (no per-append
    # re-parse); readers keep skipping the torn one, the new row lands
    ledger.append_row(_run_row(0.1), directory=d)
    rows = ledger.read_rows(directory=d)
    assert len(rows) == len(NOISE) + 1
    assert rows[-1]["warm_median_epoch_s"] == 0.1


def test_keep_retention_trims_oldest(tmp_path, monkeypatch):
    monkeypatch.setenv("NTS_LEDGER_KEEP", "3")
    d = str(tmp_path)
    for i in range(6):
        ledger.append_row(_run_row(0.1 + i), directory=d)
    rows = ledger.read_rows(directory=d)
    assert len(rows) == 3
    assert [r["warm_median_epoch_s"] for r in rows] == [3.1, 4.1, 5.1]


def test_crashed_writer_leaves_previous_state(tmp_path):
    """tmp+replace: a tmp file left by a dead writer never corrupts the
    ledger readers see."""
    d = str(tmp_path)
    _seeded(d)
    tmp = os.path.join(d, ledger.LEDGER_FILENAME + ".tmp-99999")
    with open(tmp, "w") as fh:
        fh.write('{"kind": "run", "half a ro')
    assert len(ledger.read_rows(directory=d)) == len(NOISE)


def test_suite_row(tmp_path):
    d = str(tmp_path)
    ledger.append_row(ledger.suite_row(900.0, 420, 0, 1200.0), directory=d)
    rows = ledger.read_rows(directory=d)
    assert [r["kind"] for r in rows] == ["suite"]
    assert rows[0]["dots_passed"] == 420 and rows[0]["timeout_s"] == 1200.0


# ---- sentinel: the acceptance pair ------------------------------------------


def test_sentinel_seeded_noise_does_not_trip(tmp_path):
    d = str(tmp_path)
    _seeded(d)
    ledger.append_row(_run_row(0.1 * 1.10), directory=d)  # +10% noise
    result = perf_sentinel.check(
        ledger.read_rows(directory=d), "run", k=8, min_baseline=2,
        nsigma=3.0, floor=0.08, max_tol=0.5,
    )
    assert result["regressed"] == []
    m = result["metrics"]["warm_median_epoch_s"]
    assert m["delta"] == pytest.approx(0.10)
    # the MAD window sized the tolerance ABOVE the noise band
    assert m["tol"] > 0.10


def test_sentinel_25pct_regression_trips_exit_2(tmp_path, capsys):
    d = str(tmp_path)
    _seeded(d)
    ledger.append_row(_run_row(0.1 * 1.25), directory=d)  # real regression
    rc = perf_sentinel.main(["check", "--ledger", d])
    assert rc == 2
    err = capsys.readouterr().err
    assert "REGRESSION" in err and "warm_median_epoch_s" in err


def test_sentinel_json_matches_diff_shape(tmp_path, capsys):
    d = str(tmp_path)
    _seeded(d)
    ledger.append_row(_run_row(0.1 * 1.25), directory=d)
    rc = perf_sentinel.main(["check", "--ledger", d, "--json"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 2
    # the --diff contract: {tol, metrics: {m: {a, b, delta, regressed}},
    # regressed: [...]}
    assert set(out["regressed"]) == {"warm_median_epoch_s"}
    m = out["metrics"]["warm_median_epoch_s"]
    for key in ("a", "b", "delta", "regressed"):
        assert key in m
    assert m["a"] == pytest.approx(0.1) and m["b"] == pytest.approx(0.125)


def test_sentinel_thin_history_exits_0(tmp_path):
    """Fewer matching rows than --min-baseline = no gate (warned), never
    a guessed verdict."""
    d = str(tmp_path)
    ledger.append_row(_run_row(0.1), directory=d)
    ledger.append_row(_run_row(10.0), directory=d)  # wild, but baseline=1
    rc = perf_sentinel.main(["check", "--ledger", d])
    assert rc == 0


def test_sentinel_key_mismatch_rows_never_baseline(tmp_path):
    """Rows from a different graph/cfg/backend share a file but never a
    trajectory."""
    d = str(tmp_path)
    for mult in NOISE:
        ledger.append_row(
            _run_row(0.01 * mult, graph_digest="OTHER"), directory=d
        )
    ledger.append_row(_run_row(0.1), directory=d)  # 10x the others' times
    result = perf_sentinel.check(
        ledger.read_rows(directory=d), "run", k=8, min_baseline=2,
        nsigma=3.0, floor=0.08, max_tol=0.5,
    )
    assert result["regressed"] == []
    assert result["baseline_n"] == 0  # nothing matched the candidate key


def test_sentinel_wire_counter_regression_trips(tmp_path):
    d = str(tmp_path)
    _seeded(d)
    ledger.append_row(_run_row(0.1, wire=2000), directory=d)  # 2x wire
    result = perf_sentinel.check(
        ledger.read_rows(directory=d), "run", k=8, min_baseline=2,
        nsigma=3.0, floor=0.08, max_tol=0.5,
    )
    assert result["regressed"] == ["wire_bytes_fwd_per_epoch"]


def test_sentinel_hist_p99_joins_the_gate(tmp_path):
    d = str(tmp_path)
    for mult in NOISE:
        ledger.append_row(_run_row(
            0.1 * mult,
            hist_quantiles={"serve.latency_ms": {
                "count": 100, "p50": 5.0, "p95": 9.0, "p99": 10.0 * mult,
            }},
        ), directory=d)
    ledger.append_row(_run_row(
        0.1,
        hist_quantiles={"serve.latency_ms": {
            "count": 100, "p50": 5.0, "p95": 9.0, "p99": 30.0,
        }},
    ), directory=d)
    result = perf_sentinel.check(
        ledger.read_rows(directory=d), "run", k=8, min_baseline=2,
        nsigma=3.0, floor=0.08, max_tol=0.5,
    )
    assert result["regressed"] == ["hist_serve.latency_ms_p99"]


# ---- sentinel: suite rows (the "watch the margin" machine check) ------------


def test_suite_margin_warning_at_80pct(tmp_path):
    d = str(tmp_path)
    ledger.append_row(ledger.suite_row(1000.0, 420, 0, 1200.0),
                      directory=d)
    result = perf_sentinel.check(
        ledger.read_rows(directory=d), "suite", k=8, min_baseline=2,
        nsigma=3.0, floor=0.08, max_tol=0.5,
    )
    assert result.get("suite_margin_exceeded") is True
    assert any("suite_margin" in w for w in result["warnings"])
    # under the margin: no warning
    ledger.append_row(ledger.suite_row(700.0, 420, 0, 1200.0),
                      directory=d)
    result = perf_sentinel.check(
        ledger.read_rows(directory=d), "suite", k=8, min_baseline=2,
        nsigma=3.0, floor=0.08, max_tol=0.5,
    )
    assert not result.get("suite_margin_exceeded")


def test_suite_fatal_escalates_margin_to_exit_2(tmp_path):
    d = str(tmp_path)
    ledger.append_row(ledger.suite_row(1100.0, 420, 0, 1200.0),
                      directory=d)
    assert perf_sentinel.main(["check", "--ledger", d, "--kind",
                               "suite"]) == 0  # warning only by default
    assert perf_sentinel.main(["check", "--ledger", d, "--kind", "suite",
                               "--suite-fatal"]) == 2


def test_suite_dots_drop_warns(tmp_path):
    d = str(tmp_path)
    for _ in range(3):
        ledger.append_row(ledger.suite_row(600.0, 420, 0, 1200.0),
                          directory=d)
    ledger.append_row(ledger.suite_row(600.0, 390, 0, 1200.0),
                      directory=d)
    result = perf_sentinel.check(
        ledger.read_rows(directory=d), "suite", k=8, min_baseline=2,
        nsigma=3.0, floor=0.08, max_tol=0.5,
    )
    assert any("dots_passed" in w for w in result["warnings"])


def test_failed_suite_rows_never_baseline(tmp_path):
    """Timed-out/failed suite executions (nonzero rc) are excluded from
    the baseline window: their saturated durations and truncated
    DOTS_PASSED would otherwise normalize exactly the degraded state the
    gate exists to catch."""
    d = str(tmp_path)
    for _ in range(3):
        ledger.append_row(ledger.suite_row(600.0, 420, 0, 1200.0),
                          directory=d)
    for _ in range(2):  # two timeout-killed runs poison the history
        ledger.append_row(ledger.suite_row(1200.0, 150, 124, 1200.0),
                          directory=d)
    # a real duration regression vs the CLEAN 600s baseline must trip
    # (a 1200s-polluted median would wave it through)
    ledger.append_row(ledger.suite_row(900.0, 420, 0, 1200.0),
                      directory=d)
    result = perf_sentinel.check(
        ledger.read_rows(directory=d), "suite", k=8, min_baseline=2,
        nsigma=3.0, floor=0.08, max_tol=0.5,
    )
    assert result["regressed"] == ["suite_duration_s"]
    assert result["metrics"]["suite_duration_s"]["a"] == 600.0
    # and the dots-drop warning compares against the clean median too
    ledger.append_row(ledger.suite_row(600.0, 400, 0, 1200.0),
                      directory=d)
    result = perf_sentinel.check(
        ledger.read_rows(directory=d), "suite", k=8, min_baseline=2,
        nsigma=3.0, floor=0.08, max_tol=0.5,
    )
    assert any("dots_passed" in w for w in result["warnings"])


def test_missing_ledger_exits_1_not_vacuous_pass(tmp_path, capsys):
    rc = perf_sentinel.main(
        ["check", "--ledger", str(tmp_path / "nope")]
    )
    assert rc == 1
    assert "no ledger file" in capsys.readouterr().err


def test_record_suite_cli_roundtrip(tmp_path):
    d = str(tmp_path)
    rc = perf_sentinel.main([
        "record-suite", "--ledger", d, "--duration", "612", "--dots",
        "431", "--rc", "0", "--timeout", "1200",
    ])
    assert rc == 0
    rows = ledger.read_rows(directory=d)
    assert len(rows) == 1 and rows[0]["kind"] == "suite"
    assert rows[0]["suite_duration_s"] == 612.0
    assert rows[0]["dots_passed"] == 431


# ---- serve rows (ISSUE 14: serve_bench -> ledger -> sentinel) ---------------


def _serve_ledger_row(p99, **over):
    row = ledger.serve_row(
        latency_ms={"p50": p99 * 0.4, "p95": p99 * 0.8, "p99": p99},
        shed_rate=0.0, throughput_rps=100.0, requests=200,
        cfg_fingerprint="cfgfp", graph_digest="digest",
        mode="open", replicas=3, continuous_batching=True,
        delta_rate=2.0, deltas_applied=10,
    )
    row["backend"] = "cpu-test"  # pin: the real fingerprint varies per rig
    row.update(over)
    return row


def test_serve_row_key_embeds_load_shape(tmp_path):
    """A 3-replica CB open-loop row must never baseline a 1-replica
    closed-loop one — the load shape rides the cfg key."""
    a = _serve_ledger_row(40.0)
    b = _serve_ledger_row(40.0, mode="closed")
    b["cfg"] = b["cfg"].replace("open", "closed")
    assert ledger.row_key(a) != ledger.row_key(b)
    assert a["cfg"] == "cfgfp|open|r3|cb1"
    assert a["p99_ms"] == 40.0 and a["replicas"] == 3


def test_sentinel_gates_serve_p99_trend(tmp_path):
    """The serve trajectory gate: noise-band history passes, a 2x p99
    jump exits 2 — serve latency trend-gated like epoch time."""
    d = str(tmp_path)
    for mult in NOISE:
        ledger.append_row(_serve_ledger_row(40.0 * mult), directory=d)
    rc = perf_sentinel.main(["check", "--ledger", d, "--kind", "serve"])
    assert rc == 0
    ledger.append_row(_serve_ledger_row(80.0), directory=d)
    rc = perf_sentinel.main(["check", "--ledger", d, "--kind", "serve"])
    assert rc == 2


# ---- list-keys: trajectory inventory ---------------------------------------


def test_list_keys_groups_trajectories(tmp_path, capsys):
    d = str(tmp_path)
    for i in range(3):
        ledger.append_row(_run_row(0.1, ts=float(100 + i)), directory=d)
    ledger.append_row(_run_row(0.2, cfg="othercfg", ts=50.0), directory=d)
    ledger.append_row(_serve_ledger_row(10.0, ts=200.0), directory=d)
    ledger.append_row(
        ledger.fleet_row(3, 3, 0, 1, {"serve.latency_ms": {
            "count": 10, "p50": 1.0, "p95": 2.0, "p99": 3.0}}),
        directory=d,
    )

    keys = perf_sentinel.list_keys(ledger.read_rows(directory=d))
    by = {(g["kind"], g["cfg"]): g for g in keys}
    assert len(keys) == 4
    run = by[("run", "cfgfp")]
    assert run["rows"] == 3
    assert (run["first_ts"], run["last_ts"]) == (100.0, 102.0)
    assert by[("run", "othercfg")]["rows"] == 1
    serve = next(g for g in keys if g["kind"] == "serve")
    assert serve["rows"] == 1
    fleet = next(g for g in keys if g["kind"] == "fleet")
    assert fleet["graph_digest"] == "fleet" and fleet["rows"] == 1

    # the subcommand renders a table naming every trajectory
    rc = perf_sentinel.main(["list-keys", "--ledger", d])
    out = capsys.readouterr().out
    assert rc == 0
    assert "4 trajectory key(s) across 6 row(s)" in out
    for needle in ("run", "serve", "fleet", "othercfg", "last_seen"):
        assert needle in out


def test_list_keys_flag_and_json(tmp_path, monkeypatch, capsys):
    monkeypatch.setenv("NTS_LEDGER_DIR", str(tmp_path))
    ledger.append_row(_run_row(0.1), directory=str(tmp_path))
    rc = perf_sentinel.main(["--list-keys"])  # shorthand for the subcmd
    assert rc == 0
    assert "1 trajectory key(s)" in capsys.readouterr().out

    rc = perf_sentinel.main(["list-keys", "--ledger", str(tmp_path),
                             "--json"])
    assert rc == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["keys"][0]["kind"] == "run"
    assert payload["keys"][0]["rows"] == 1


def test_list_keys_missing_ledger_exits_1(tmp_path, capsys):
    rc = perf_sentinel.main(
        ["list-keys", "--ledger", str(tmp_path / "nowhere")]
    )
    assert rc == 1
