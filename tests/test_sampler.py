"""Sampler + mini-batch path tests (the testcsr.cpp role, SURVEY.md 4.1)."""

import numpy as np
import pytest

import jax.numpy as jnp

from tests.conftest import tiny_graph
from neutronstarlite_tpu.graph.dataset import GNNDatum
from neutronstarlite_tpu.models import get_algorithm
from neutronstarlite_tpu.models.gcn_sample import GCNSampleTrainer
from neutronstarlite_tpu.ops.minibatch import minibatch_gather
from neutronstarlite_tpu.sample.sampler import Sampler
from neutronstarlite_tpu.utils.config import InputInfo
from tests.test_models import _planted_cfg, _planted_data


def test_sampler_respects_fanout_and_shapes(rng):
    g, _ = tiny_graph(rng, v_num=80, e_num=600)
    seeds = rng.choice(80, size=30, replace=False)
    s = Sampler(g, seeds, batch_size=8, fanouts=[3, 5], seed=1)
    batches = list(s.sample_epoch())
    assert len(batches) == 4  # ceil(30/8)
    for b in batches:
        # static shapes across batches
        assert b.seeds.shape == (8,)
        assert [n.shape[0] for n in b.nodes] == s.node_caps
        for h, hop in enumerate(b.hops):
            assert hop.src_local.shape[0] == s.node_caps[h + 1] * s.fanouts[h]
        # per-dst sampled degree <= fanout
        for h, hop in enumerate(b.hops):
            real = hop.weight > 0
            if real.any():
                counts = np.bincount(hop.dst_local[real])
                assert counts.max() <= s.fanouts[h]
        # sampled edges are real graph edges
        hop = b.hops[-1]  # seed-adjacent hop
        real = hop.weight > 0
        srcs = b.nodes[-2][hop.src_local[real]]
        dsts = b.nodes[-1][hop.dst_local[real]]
        edge_set = set(zip(g.row_indices.tolist(), g.dst_of_edge.tolist()))
        for u, v in zip(srcs, dsts):
            assert (u, v) in edge_set


def test_sampler_full_fanout_equals_exact_aggregation(rng):
    """With fanout >= max in-degree, one sampled hop must equal the exact
    weighted neighbor sum (the testcsr ones-tensor check, test/testcsr.cpp)."""
    g, dense = tiny_graph(rng, v_num=40, e_num=200)
    seeds = np.arange(40)
    fan = int(g.in_degree.max())
    s = Sampler(g, seeds, batch_size=40, fanouts=[fan], seed=0)
    (b,) = list(s.sample_epoch(shuffle=False))
    x = rng.standard_normal((40, 6)).astype(np.float32)
    hop = b.hops[0]
    x_in = x[b.nodes[0]]
    out = np.asarray(
        minibatch_gather(
            jnp.asarray(hop.src_local), jnp.asarray(hop.dst_local),
            jnp.asarray(hop.weight), jnp.asarray(x_in), s.node_caps[1],
        )
    )
    expected = dense @ x.astype(np.float64)
    real = b.seed_mask > 0
    np.testing.assert_allclose(
        out[real], expected[b.seeds[real]], rtol=1e-4, atol=1e-4
    )


def test_gcn_sample_converges_on_planted_partition():
    cfg = _planted_cfg(epochs=30)
    cfg.algorithm = "GCNSAMPLESINGLE"
    cfg.fanout_string = "5-5"
    cfg.batch_size = 32
    src, dst, datum = _planted_data(seed=11)
    trainer = GCNSampleTrainer.from_arrays(cfg, src, dst, datum)
    result = trainer.run()
    assert result["acc"]["test"] > 0.75, result
    assert get_algorithm("GCNSAMPLESINGLE") is GCNSampleTrainer


def test_native_hub_sampling_distinct_and_uniform():
    """The O(fanout) Floyd branch (deg > 8*fanout) must return DISTINCT
    valid in-neighbors with per-neighbor inclusion roughly uniform — the
    same distribution as the reservoir it replaces for hub destinations."""
    from neutronstarlite_tpu import native

    if not native.available():
        pytest.skip("native runtime unavailable")
    deg, fanout, trials = 10_000, 8, 400
    # star graph: vertex 0 has in-edges from 1..deg
    column_offset = np.zeros(deg + 2, dtype=np.int64)
    column_offset[1:] = deg  # only vertex 0 has in-edges
    row_indices = np.arange(1, deg + 1, dtype=np.int32)
    counts = np.zeros(deg, dtype=np.int64)
    for t in range(trials):
        src, dst_idx = native.sample_hop(
            column_offset, row_indices, np.zeros(1, dtype=np.int64),
            fanout, seed=1000 + t,
        )
        assert len(src) == fanout
        assert len(np.unique(src)) == fanout  # distinct
        assert src.min() >= 1 and src.max() <= deg  # valid neighbors
        counts[src - 1] += 1
    # inclusion probability fanout/deg; over `trials` draws the count of any
    # single neighbor is Binomial(trials, 8e-4) — just assert the spread is
    # sane (no neighbor hugely over-represented, total conserved)
    assert counts.sum() == trials * fanout
    assert counts.max() <= 8, counts.max()  # P(X >= 9) astronomically small


def _batches_equal(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x.seeds, y.seeds)
        np.testing.assert_array_equal(x.seed_mask, y.seed_mask)
        for nx, ny in zip(x.nodes, y.nodes):
            np.testing.assert_array_equal(nx, ny)
        for hx, hy in zip(x.hops, y.hops):
            np.testing.assert_array_equal(hx.src_local, hy.src_local)
            np.testing.assert_array_equal(hx.dst_local, hy.dst_local)
            np.testing.assert_allclose(hx.weight, hy.weight)
            assert hx.n_dst == hy.n_dst


def test_parallel_sampler_worker_count_is_pure_throughput(rng):
    """sample/parallel.py contract: batches are seeded per (epoch, index),
    so 0, 1 and 3 workers must produce BIT-IDENTICAL epochs in order."""
    from neutronstarlite_tpu.graph.storage import build_graph
    from neutronstarlite_tpu.sample.parallel import ParallelEpochSampler

    V, E = 300, 2400
    src = rng.integers(0, V, size=E, dtype=np.uint32)
    dst = rng.integers(0, V, size=E, dtype=np.uint32)
    g = build_graph(src, dst, V, weight="gcn_norm")
    seeds = np.arange(0, V, 2)

    def epoch(workers, e=1):
        # spawn context: jax is already live in the pytest process, so the
        # fork pool would (rightly) degrade to inline AND CPython would
        # emit the os.fork-under-threads RuntimeWarning; the pickling pool
        # exercises the same queue/reorder protocol warning-free
        s = ParallelEpochSampler(
            g, seeds, 32, [4, 3], seed=9, workers=workers, ctx_method="spawn"
        )
        try:
            return list(s.sample_epoch(e))
        finally:
            s.close()

    inline = epoch(0)
    assert len(inline) == -(-len(seeds) // 32)
    _batches_equal(inline, epoch(1))
    _batches_equal(inline, epoch(3))
    # different epoch -> different shuffle/samples
    other = epoch(0, e=2)
    assert any(
        not np.array_equal(a.seeds, b.seeds) for a, b in zip(inline, other)
    )


def test_parallel_sampler_trains():
    """GCNSampleTrainer with multi-worker sampling, in a PRISTINE process
    (the production shape: the pool forks before the first JAX backend
    touch, so the fork-safety gate stays open) — must converge and report
    the worker count it was given."""
    import json
    import os
    import subprocess
    import sys

    prog = r"""
import json
import numpy as np
from neutronstarlite_tpu.graph.dataset import GNNDatum
from neutronstarlite_tpu.graph.synthetic import planted_partition_graph
from neutronstarlite_tpu.models.gcn_sample import GCNSampleTrainer
from neutronstarlite_tpu.utils.config import InputInfo

v_num, classes, f = 240, 3, 12
src, dst, feature, label = planted_partition_graph(
    v_num, classes, avg_degree=10, feature_size=f, seed=4
)
mask = (np.arange(v_num) % 3).astype(np.int32)
datum = GNNDatum(feature=feature, label=label.astype(np.int32), mask=mask)
cfg = InputInfo()
cfg.algorithm = "GCNSAMPLESINGLE"
cfg.vertices = v_num
cfg.layer_string = f"{f}-16-{classes}"
cfg.fanout_string = "4-4"
cfg.batch_size = 32
cfg.epochs = 10
cfg.learn_rate = 0.02
cfg.drop_rate = 0.0
cfg.decay_epoch = -1
tr = GCNSampleTrainer.from_arrays(cfg, src, dst, datum)
result = tr.run()
print(json.dumps({
    "workers": tr.sample_workers,
    "train_acc": result["acc"]["train"],
}))
"""
    env = dict(os.environ)
    env["NTS_SAMPLE_WORKERS"] = "2"
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        timeout=300, env=env,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["workers"] == 2, out
    assert out["train_acc"] > 0.8, out


def test_parallel_sampler_degrades_inline_with_live_jax(rng):
    """With a live JAX backend in-process (this pytest process) the pool
    must refuse to fork by default and degrade to inline sampling."""
    import jax

    jax.random.PRNGKey(0)  # ensure the backend is initialized
    from neutronstarlite_tpu.graph.storage import build_graph
    from neutronstarlite_tpu.sample.parallel import ParallelEpochSampler

    V = 64
    src = rng.integers(0, V, size=300, dtype=np.uint32)
    dst = rng.integers(0, V, size=300, dtype=np.uint32)
    g = build_graph(src, dst, V, weight="gcn_norm")
    s = ParallelEpochSampler(g, np.arange(V), 16, [3], seed=1, workers=4)
    assert s.workers == 0 and s._in_q is None
    assert len(list(s.sample_epoch(0))) == 4


def test_sampler_injectable_rng_reproduces_fanouts(rng):
    """An injected numpy Generator drives the draws (no monkeypatching):
    same Generator state => bit-identical batches; the serving path and
    tests rely on this (ISSUE 3 satellite)."""
    g, _ = tiny_graph(rng, v_num=60, e_num=400)
    seeds = np.arange(60)

    def batches(sampler):
        return [
            (b.nodes, b.hops, b.seeds) for b in sampler.sample_epoch(shuffle=False)
        ]

    a = Sampler(g, seeds, batch_size=16, fanouts=[3, 4],
                rng=np.random.default_rng(77))
    b = Sampler(g, seeds, batch_size=16, fanouts=[3, 4],
                rng=np.random.default_rng(77))
    # the injected Generator implies the NumPy path even when the native
    # sampler is available (it would ignore the Generator)
    assert not a.use_native and not b.use_native
    for (na, ha, sa), (nb, hb, sb) in zip(batches(a), batches(b)):
        np.testing.assert_array_equal(sa, sb)
        for x, y in zip(na, nb):
            np.testing.assert_array_equal(x, y)
        for hx, hy in zip(ha, hb):
            np.testing.assert_array_equal(hx.src_local, hy.src_local)
            np.testing.assert_array_equal(hx.dst_local, hy.dst_local)
            np.testing.assert_array_equal(hx.weight, hy.weight)
    # default path unchanged: seed-based construction still works
    c = Sampler(g, seeds, batch_size=16, fanouts=[3, 4], seed=5)
    assert isinstance(c.rng, np.random.Generator)
    # contradictory args: the native sampler cannot honor an injected rng
    with pytest.raises(ValueError, match="use_native"):
        Sampler(g, seeds, batch_size=16, fanouts=[3], use_native=True,
                rng=np.random.default_rng(1))


def test_sampler_sample_batch_validates_and_pads(rng):
    g, _ = tiny_graph(rng, v_num=40, e_num=250)
    s = Sampler(g, np.arange(40), batch_size=8, fanouts=[3],
                rng=np.random.default_rng(3))
    b = s.sample_batch(np.array([5, 9, 11]))
    assert b.seeds.shape == (8,)
    assert b.seed_mask[:3].sum() == 3 and b.seed_mask[3:].sum() == 0
    np.testing.assert_array_equal(b.seeds[:3], [5, 9, 11])
    with pytest.raises(ValueError):
        s.sample_batch(np.arange(9))  # exceeds batch capacity
    with pytest.raises(ValueError):
        s.sample_batch(np.empty(0, np.int64))
