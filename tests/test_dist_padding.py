"""Dist-layout padding bounds (VERDICT round-2 item 6).

The single-chip ELL layout has a test-enforced waste bound; these pin the
DISTRIBUTED layouts on a power-law fixture — the degree regime where the
uniform [P, P, Eb] layout degrades (the dominant diagonal blocks set the
global max and every remote block pays it). Contracts:

- the step-major ring layout (DistGraph.step_blocks, what the ring
  actually ships) wastes strictly less than the uniform layout and stays
  under an absolute bound;
- DistEll / DistBlockedEll slot waste stays bounded on the same fixture;
- the step-major layout is exact: re-expanding it reproduces every edge.
"""

from __future__ import annotations

import numpy as np

from neutronstarlite_tpu.graph.storage import build_graph
from neutronstarlite_tpu.graph.synthetic import synthetic_power_law_graph
from neutronstarlite_tpu.parallel.dist_ell import DistEllPair
from neutronstarlite_tpu.parallel.dist_graph import DistGraph


def _power_law_rig(P=8, v_num=4096, e_num=40000):
    src, dst = synthetic_power_law_graph(v_num, e_num, seed=11)
    g = build_graph(src, dst, v_num, weight="gcn_norm")
    dg = DistGraph.build(g, P, edge_chunk=256)
    return g, dg


def test_step_major_ring_padding_bounded():
    g, dg = _power_law_rig()
    uniform = dg.padding_stats()
    step = dg.step_padding_stats()
    assert step["real_edges"] == uniform["real_edges"] == g.e_num
    # strictly better than the uniform layout on a power-law graph...
    assert step["waste_ratio"] < uniform["waste_ratio"]
    # ...and absolutely bounded: per-step cross-device max + edge_chunk
    # rounding. 2x is generous headroom over the observed ~1.3x; a layout
    # regression (e.g. re-padding to the global max) trips it immediately.
    assert step["waste_ratio"] <= 2.0, step


def test_step_blocks_exactly_cover_edges():
    """Expanding the step-major arrays must reproduce the whole edge set
    (global ids, with multiplicity) — padding is weight-0 slots only."""
    g, dg = _power_law_rig(P=4, v_num=512, e_num=4000)
    rb = dg.step_blocks()
    P = dg.partitions
    got = []
    for s in range(P):
        bs, bd, bw = (np.asarray(rb.src[s]), np.asarray(rb.dst[s]),
                      np.asarray(rb.wgt[s]))
        for p in range(P):
            q = (p + s) % P
            n = int(dg.block_count[p, q])
            got.append(np.stack([
                bs[p, :n] + dg.offsets[q],
                bd[p, :n] + dg.offsets[p],
            ], axis=1))
            # padding slots beyond n carry weight 0
            assert not bw[p, n:].any()
    got = np.concatenate(got)
    want = np.stack([g.row_indices, g.dst_of_edge], axis=1).astype(np.int64)
    order_a = np.lexsort((got[:, 0], got[:, 1]))
    order_b = np.lexsort((want[:, 0], want[:, 1]))
    np.testing.assert_array_equal(got[order_a], want[order_b])


def test_dist_ell_slot_waste_bounded():
    g, dg = _power_law_rig()
    pair = DistEllPair.build(dg)
    stats = pair.padding_stats(g.e_num)
    # sources of padding: a degree's rounding up to its level's width and
    # the cross-device row max per level. Mean degree 10 over eight devices:
    # most rows sit in the 4- and 8-wide levels, whose alignment no choice
    # removes. Observed 1.98x / 2.01x with the widths chosen from the degree
    # histogram (the power-of-two ladder: 3.23x / 3.13x under a 4x bound) —
    # a level-assignment regression trips this
    assert stats["fwd_waste_ratio"] <= 2.3, stats
    assert stats["bwd_waste_ratio"] <= 2.3, stats


def test_dist_blocked_slot_waste_bounded():
    """Blocked-layout waste is density-sensitive (every (tile, dst) run
    pads to >= _MIN_K slots, so sparse tiles cost more); the fixture uses
    a source tile sized for a few edges per run — the regime the layout
    is for — and pins the stacked cross-device overhead under 2x of the
    per-device blocked waste."""
    from neutronstarlite_tpu.ops.blocked_ell import BlockedEllPair
    from neutronstarlite_tpu.parallel.dist_blocked import DistBlockedEllPair

    g, dg = _power_law_rig(P=4, v_num=2048, e_num=60000)
    pair = DistBlockedEllPair.build(dg, vt=512)
    stats = pair.padding_stats(g.e_num)
    single = BlockedEllPair.from_host(g, vt=512)
    single_waste = sum(
        int(np.prod(np.asarray(n).shape)) for n in single.fwd.nbr
    ) / g.e_num
    assert stats["fwd_waste_ratio"] <= 4.0, stats
    assert stats["bwd_waste_ratio"] <= 4.0, stats
    assert stats["fwd_waste_ratio"] <= 2.0 * single_waste, (stats, single_waste)
