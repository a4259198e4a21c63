"""The comparison that decides ``correct``, on a small graph: blocks drawn
here, by hand, from the benchmark's edge list pass; each way a sampler can
go wrong is named."""

import numpy as np
import pytest

from harness import correct, data, spec

check = spec.named_module("checks", "vertex_graph")

GRAPH = {"generator": "power_law", "vertices": 300, "edges": 6000, "seed": 5,
         "exponent": 2.0, "self_loops": True, "symmetric": False}
FANOUTS = (4, 3)
CAPS = (6 * 3 * 4, 6 * 3, 6)  # six seeds; each level holds the draws of the one above


@pytest.fixture(scope="module")
def graph(tmp_path_factory):
    return check.ReferenceGraph({"reference": "gcn"}, GRAPH, str(tmp_path_factory.mktemp("cache")))


def draw_blocks(graph, seeds, n_real, rng):
    """(nodes, hops) in the fused sampler's layout, drawn from the
    reference's own edge list."""
    nodes = [None] * 3
    hops = [None] * 2
    nodes[2] = np.asarray(seeds, np.int32)
    live = n_real
    for h in (1, 0):
        fanout, dcap, ncap = FANOUTS[h], CAPS[h + 1], CAPS[h]
        src = np.zeros((dcap, fanout), np.int32)
        valid = np.zeros((dcap, fanout), bool)
        for row in range(live):
            v = nodes[h + 1][row]
            run = graph.src[graph.offsets[v]:graph.offsets[v + 1]]
            pick = rng.choice(len(run), size=min(len(run), fanout), replace=False)
            src[row, :len(pick)] = run[pick]
            valid[row, :len(pick)] = True
        uniq = np.unique(src[valid])
        nodes[h] = np.zeros(ncap, np.int32)
        nodes[h][:len(uniq)] = uniq
        rows = np.repeat(np.arange(dcap, dtype=np.int32), fanout)
        flat = valid.reshape(-1)
        hops[h] = (np.where(flat, np.searchsorted(uniq, src.reshape(-1)), 0).astype(np.int32),
                   np.where(flat, rows, 0), flat.astype(np.float32))
        live = len(uniq)
    return nodes, hops


def test_sorted_edges_are_the_generators_and_are_kept(graph, tmp_path):
    src, dst = data.make_edges(GRAPH)
    assert np.all(np.diff(graph.dst.astype(np.int64)) >= 0)
    assert sorted(zip(src.tolist(), dst.tolist())) == sorted(zip(graph.src.tolist(), graph.dst.tolist()))
    by_src = graph.by_src
    assert np.all(np.diff(by_src.into.astype(np.int64)) >= 0)
    assert sorted(zip(by_src.into.tolist(), by_src.take.tolist())) == sorted(zip(src.tolist(), dst.tolist()))
    again = check.ReferenceGraph({"reference": "gcn"}, GRAPH, graph.cache_root)  # from the cache
    assert np.array_equal(again.src, graph.src) and np.array_equal(again.dst, graph.dst)


def test_has_edges(graph):
    src, dst = graph.src[::7].astype(np.int64), graph.dst[::7].astype(np.int64)
    assert graph.has_edges(src, dst).all()
    pairs = set(zip(graph.src.tolist(), graph.dst.tolist()))
    fake = [(u, v) for u in range(40) for v in range(40) if (u, v) not in pairs][:50]
    u, v = np.asarray(fake).T
    assert not graph.has_edges(u, v).any()


def test_blocks_drawn_from_the_graph_have_no_fault(graph):
    rng = np.random.default_rng(0)
    nodes, hops = draw_blocks(graph, [5, 17, 100, 250, 0, 0], 4, rng)  # two padding seeds
    assert check.block_faults(graph, nodes, hops, FANOUTS, 4, table_width=512) == []


def test_each_fault_of_a_sampler_is_named(graph):
    rng = np.random.default_rng(1)
    seeds = [5, 17, 100, 250, 7, 9]
    nodes, hops = draw_blocks(graph, seeds, 6, rng)

    def faults(nodes, hops):
        return check.block_faults(graph, nodes, hops, FANOUTS, 6, table_width=512)

    # a draw that is no edge: point a slot of the seed hop at a vertex that
    # is not an in-neighbour of its seed
    src_local, dst_local, w = (a.copy() for a in hops[1])
    run = set(graph.src[graph.offsets[5]:graph.offsets[6]].tolist())
    stranger = next(i for i, u in enumerate(nodes[1][:18]) if u not in run and nodes[1][i] != 0)
    src_local[0] = stranger
    assert any("not edges of the graph" in f for f in faults(nodes, [hops[0], (src_local, dst_local, w)]))

    # a destination that drew too few
    src_local, dst_local, w = (a.copy() for a in hops[1])
    w[0] = 0.0
    assert any("another number of neighbours" in f
               for f in faults(nodes, [hops[0], (src_local, dst_local, w)]))

    # a level that is not the sorted distinct sources of the level above
    swapped = [n.copy() for n in nodes]
    swapped[1][[0, 1]] = swapped[1][[1, 0]]
    assert any("sorted distinct" in f for f in faults(swapped, hops))

    # a padding seed that drew
    assert any("another number of neighbours" in f
               for f in check.block_faults(graph, nodes, hops, FANOUTS, 5, table_width=512))


def test_check_blocks_holds_logits_and_gradients_to_the_reference(graph):
    rng = np.random.default_rng(2)
    ref = graph.ref
    nodes, hops = draw_blocks(graph, [5, 17, 100, 250, 7, 9], 6, rng)
    feature = rng.standard_normal((300, 8)).astype(np.float32)
    params = [{"W": rng.standard_normal((8, 6)).astype(np.float32)},
              {"W": rng.standard_normal((6, 4)).astype(np.float32)}]
    own = ref.block_weights(nodes, [(s, d, w > 0) for s, d, w in hops],
                            graph.out_degree, graph.in_degree)
    label = rng.integers(0, 4, 6).astype(np.int32)
    mask01 = np.ones(6, np.float32)
    _, grads = ref.block_loss_and_grads(params, feature[nodes[0]], own, CAPS, label, mask01)
    case = {"nodes": nodes, "hops": own, "caps": CAPS, "fanouts": FANOUTS, "n_real": 6,
            "table_width": 512, "logits": ref.block_forward(params, feature[nodes[0]], own, CAPS),
            "grads": grads, "grad_params": params, "label": label, "mask01": mask01}
    tolerance = {"logits_rel": 0.01, "grads_rel": 0.01}

    def passes(errors, faults):
        return correct.passes(correct.compare(errors, tolerance, faults=len(faults)))

    good, faults = check.check_blocks(graph, params, feature, [case])
    assert good["logits_rel"] < 1e-6 and good["grads_rel"] < 1e-6 and faults == []
    assert passes(good, faults)

    # a program that weighs an edge wrongly shows in the logits: the
    # reference weighs the blocks itself
    heavy = [(s, d, w * np.float32(1.1)) for s, d, w in own]
    wrong = dict(case, logits=ref.block_forward(params, feature[nodes[0]], heavy, CAPS))
    bad, faults = check.check_blocks(graph, params, feature, [wrong])
    assert bad["logits_rel"] > 0.1 and not passes(bad, faults)

    # a wrong gradient fails by itself
    flipped = dict(case, grads=[{"W": -g["W"]} for g in grads])
    bad, faults = check.check_blocks(graph, params, feature, [flipped])
    assert bad["logits_rel"] < 1e-6 and bad["grads_rel"] > 1.0 and not passes(bad, faults)

    # gradients are compared at the weights they were taken at, which need
    # not be the weights of the logits
    other = [{"W": p["W"] * np.float32(0.5)} for p in params]
    _, other_grads = ref.block_loss_and_grads(other, feature[nodes[0]], own, CAPS, label, mask01)
    errors, _ = check.check_blocks(graph, params, feature,
                                   [dict(case, grads=other_grads, grad_params=other)])
    assert errors["logits_rel"] < 1e-6 and errors["grads_rel"] < 1e-6


def test_errors_and_passes():
    want = np.asarray([[1.0, -4.0], [2.0, 0.0]])
    assert correct.relative_error(want + 0.04, want) == pytest.approx(0.01)
    assert correct.relative_error(np.full_like(want, np.nan), want) == float("inf")
    assert correct.norm_error(np.asarray([3.0, 4.0 + 0.5]), np.asarray([3.0, 4.0])) == pytest.approx(0.1)
    assert correct.gradient_error([{"W": want * 1.02}, {"W": want}], [{"W": want}, {"W": want}]) \
        == pytest.approx(0.02)
    limits = {"logits_rel": 0.02, "grads_rel": 0.05}

    def passes(errors, **counts):
        return correct.passes(correct.compare(errors, limits, **counts))

    assert passes({"logits_rel": 0.01, "grads_rel": 0.04}, faults=0, losses_not_finite=0)
    assert not passes({"logits_rel": 0.03, "grads_rel": 0.04})
    assert not passes({"logits_rel": 0.01, "grads_rel": 0.06})
    assert not passes({"logits_rel": 0.01, "grads_rel": float("nan")})
    assert not passes({"logits_rel": 0.01, "grads_rel": 0.04}, faults=1)
    assert not passes({"logits_rel": 0.01, "grads_rel": 0.04}, losses_not_finite=2)
    config = {"tolerance": dict(limits, reason="..."), "rehearse": {"tolerance": {"grads_rel": 0.2}}}
    assert correct.tolerance(config, rehearse=False) == limits
    assert correct.tolerance(config, rehearse=True) == {"logits_rel": 0.02, "grads_rel": 0.2}
    assert correct.losses_not_finite([3.0, 2.5]) == 0
    assert correct.losses_not_finite([3.0, float("nan"), float("inf")]) == 2
    assert correct.losses_not_finite([]) == 1


@pytest.mark.parametrize("errors, limits, why", [
    ({"logits_rel": 0.01}, {"logits_rel": 0.02, "grads_rel": 0.05}, "a limit with no error"),
    ({"logits_rel": 0.01, "drift": 0.0}, {"logits_rel": 0.02}, "an error with no limit"),
    ({}, {}, None),
])
def test_a_name_on_one_side_only_fails(errors, limits, why):
    compared = correct.compare(errors, limits, faults=0)
    assert correct.passes(compared) == (why is None)
    one_sided = [k for k, c in compared.items() if c["value"] is None or c["limit"] is None]
    assert bool(one_sided) == (why is not None)


def test_compared_numbers_print_as_json():
    import json

    compared = correct.compare({"logits_rel": float("inf"), "grads_rel": 0.5}, {"grads_rel": 0.05})
    line = json.dumps(correct.printable(compared), allow_nan=False)
    assert json.loads(line) == {"logits_rel": {"value": "inf", "limit": None},
                                "grads_rel": {"value": 0.5, "limit": 0.05}}
