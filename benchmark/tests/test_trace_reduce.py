"""The reduction from a trace to metrics, on small traces recorded on a
TPU v5e (fixtures/, PR 22) and on hand-made event lists.

v5e_1chip.xplane.pb: four runs of one jitted step (a scan of three
iterations of gather, sort, top-k, matmul) 20 ms of host sleep apart, each
under a ``bench_step`` annotation.
"""

import os

import numpy as np
import pytest

from conftest import FIXTURES
from harness import trace_reduce as tr

ONE_CHIP = os.path.join(FIXTURES, "v5e_1chip.xplane.pb")


def raster(intervals, lo, hi, step=1e-7):
    """Seconds covered, counted on a grid: an independent check of the
    sweep in ``union``."""
    n = int(round((hi - lo) / step))
    grid = np.zeros(n, dtype=bool)
    for a, b in intervals:
        i, j = int(round((a - lo) / step)), int(round((b - lo) / step))
        grid[max(i, 0):max(min(j, n), 0)] = True
    return grid.sum() * step


def test_parse_instruction():
    text = ("%fusion.5 = bf16[320000,128]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[20000,128]"
            "{1,0:T(8,128)(2,1)} %tbl.1), kind=kLoop, calls=%fused_computation.7")
    assert tr.parse_instruction(text) == ("fusion", "fusion", "bf16[320000,128]")
    text = ("%copy-start = (bf16[128,128]{1,0:T(8,128)(2,1)S(1)}, bf16[128,128]{1,0:T(8,128)(2,1)}, "
            "u32[]{:S(2)}) copy-start(bf16[128,128]{1,0:T(8,128)(2,1)} %w.1)")
    assert tr.parse_instruction(text) == (
        "copy-start", "copy-start", "(bf16[128,128], bf16[128,128], u32[])")
    text = "%all-gather-start.3 = (f32[8,4]{1,0}, f32[32,4]{1,0}) all-gather-start(f32[8,4]{1,0} %x), dimensions={0}"
    name, opcode, _ = tr.parse_instruction(text)
    assert (name, opcode) == ("all-gather-start", "all-gather-start")
    assert tr.is_collective(opcode) and not tr.is_collective("fusion")
    assert tr.parse_instruction("not an instruction")[1] == "unknown"


def test_interval_arithmetic():
    u = tr.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)])
    assert u == [(0, 3), (5, 7)]
    assert tr.total(u) == 5
    assert tr.clip(u, 2, 6) == [(2, 3), (5, 6)]
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6)]) == [(0, 1), (2, 4), (6, 10)]
    assert tr.subtract([(0, 3), (5, 7)], [(2, 6)]) == [(0, 2), (6, 7)]
    assert tr.subtract([(0, 3)], []) == [(0, 3)]


def op(start, end, opcode, name=None):
    return tr.Op(float(start), float(end), name or opcode, opcode, "f32[1]")


def test_self_time_takes_nested_events_out_once():
    ops = [op(0, 10, "while"), op(1, 4, "fusion"), op(5, 9, "call"), op(6, 8, "sort")]
    got = {o.opcode: t for o, t in tr.self_times(ops)}
    assert got == {"while": 10 - 3 - 4, "fusion": 3, "call": 4 - 2, "sort": 2}


def test_collective_exposure_on_a_hand_made_device():
    # compute 0-4 and 6-9; an async all-gather 3-7 (hidden 3-4 and 6-7,
    # exposed 4-6); a synchronous all-reduce 9-10 with nothing beside it
    ops = [op(0, 4, "fusion"), op(6, 9, "fusion"), op(9, 10, "all-reduce"),
           op(3, 3.1, "all-gather-start"), op(6.9, 7, "all-gather-done")]
    async_ops = [op(3, 7, "all-gather-start")]
    d = tr.reduce_device(0, ops, async_ops, (0.0, 12.0))
    assert d.busy_s == pytest.approx(4 + 4)  # 0-4 and 6-10: async spans are not busy
    assert d.collective_s == pytest.approx(4 + 1)
    assert d.collective_exposed_s == pytest.approx(2 + 1)


def test_a_container_is_not_busy_and_has_no_time_of_its_own():
    # a scanned epoch: the ``while`` spans its body; the device runs
    # nothing between the body's operations
    ops = [op(0, 10, "while"), op(1, 4, "fusion"), op(6, 8, "sort"), op(11, 12, "fusion")]
    d = tr.reduce_device(0, ops, [], (0.0, 12.0))
    assert d.busy == [(1.0, 4.0), (6.0, 8.0), (11.0, 12.0)]
    assert d.by_group == {"fusion fusion f32[1]": 4.0, "sort sort f32[1]": 2.0}
    assert sum(d.by_group.values()) == d.busy_s


def test_recorded_trace_busy_union_and_idle_share():
    red = tr.reduce_file(ONE_CHIP, "no such annotation")  # window: extent of the device events
    assert len(red.devices) == 1 and red.devices[0].ordinal == 0
    from jax.profiler import ProfileData

    plane = next(p for p in ProfileData.from_file(ONE_CHIP).planes if p.name == "/device:TPU:0")
    line = next(l for l in plane.lines if l.name == tr.OPS_LINE)
    raw = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9) for e in line.events
           if tr.parse_instruction(e.name)[1] not in tr.CONTAINER_OPCODES]
    lo, hi = red.window
    assert red.busy_s == pytest.approx(raster(raw, lo, hi), rel=2e-3)
    # four steps of 3.39 ms in a 77.8 ms window
    assert red.busy_s == pytest.approx(4 * 3.393e-3, rel=0.01)
    assert red.window_s == pytest.approx(77.8e-3, rel=0.01)
    assert red.idle_share == pytest.approx(1 - 4 * 3.393 / 77.8, abs=0.005)
    # self times partition the busy time
    assert sum(red.devices[0].by_group.values()) == pytest.approx(red.busy_s, rel=1e-3)


def test_recorded_trace_groups_ops_and_names_gaps():
    red = tr.reduce_file(ONE_CHIP, "no such annotation")
    top = red.top_ops(10)
    assert len(top) == 10 and top == sorted(top, key=lambda t: -t[1])
    labels = [label for label, _ in top]
    assert labels[0] == "fusion fusion bf16[320000,128]"  # the row gather
    assert any(label.startswith("sort sort") for label in labels)
    d = red.devices[0]
    assert not any(label.startswith("while ") for label in d.by_group)
    assert d.collective_s == 0.0 and d.collective_exposed_s == 0.0
    gaps = red.idle_gaps(10)
    assert gaps[0][0] == "$time sleep"  # the host slept between the steps
    assert gaps[0][1] == pytest.approx(3 * 21.6e-3, rel=0.05)
    assert sum(s for _, s in gaps) == pytest.approx(red.window_s - red.busy_s, rel=1e-6)


def test_window_annotation_places_the_window():
    red = tr.reduce_file(ONE_CHIP, "bench_step")  # the last of the four
    assert red.window_s == pytest.approx(4.07e-3, rel=0.02)
    assert 0 < red.busy_s <= red.window_s


FOUR_CHIPS = os.path.join(FIXTURES, "v5e_4chip.xplane.pb")


def test_recorded_four_chip_trace_collectives():
    # three runs of a shard_map step under one ``benchmark_window``: an
    # all-gather of [4096, 256] shards, a reduction over the gathered slab,
    # a psum (all-reduce) and a local product; XLA ran both collectives as
    # synchronous ops, so nothing hides them
    red = tr.reduce_file(FOUR_CHIPS, "benchmark_window")
    assert [d.ordinal for d in red.devices] == [0, 1, 2, 3]
    for d in red.devices:
        assert d.busy_s == pytest.approx(194e-6, rel=0.02)
        assert d.collective_s == pytest.approx(155e-6, rel=0.02)
        assert d.collective_exposed_s == pytest.approx(d.collective_s)
    assert red.share(lambda d: d.collective_s) == pytest.approx(0.796, abs=0.01)
    assert red.busy_s == pytest.approx(sum(d.busy_s for d in red.devices) / 4)
    assert red.window_s == pytest.approx(19.87e-3, rel=0.01)
    assert red.idle_share == pytest.approx(0.990, abs=0.002)
    top = red.top_ops(3)
    assert top[0][0] == "all-gather all-gather bf16[16384,256]"
    assert top[0][1] == pytest.approx(4 * 148e-6, rel=0.03)  # summed over the chips
    assert any(label.startswith("all-reduce psum_invariant") for label, _ in red.top_ops(10))


def test_fewer_devices_than_the_trace_holds():
    red = tr.reduce_file(FOUR_CHIPS, "benchmark_window", n_devices=1)
    assert [d.ordinal for d in red.devices] == [0]
