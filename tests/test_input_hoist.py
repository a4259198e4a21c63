"""The standard-order GCN's layer-1 aggregate, computed once per run.

``GCNTrainer`` and ``DistGCNTrainer`` aggregate the constant features in
the funnel's ``input_aggregate`` phase and hand every step the result as
its feature argument (models/gcn.py, models/gcn_dist.py). Pinned here: the
hoisted forward is the un-hoisted one (logits, gradients, loss trajectory,
one chip and dist), the step programs lost the f0-wide pass and nothing
else, every other trainer keeps its programs, no float32 feature table
stays on the device under bfloat16, and serving over a full-batch GCN
toolkit still reads the raw rows.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from neutronstarlite_tpu.graph.dataset import GNNDatum
from neutronstarlite_tpu.graph.storage import build_graph
from neutronstarlite_tpu.models import get_algorithm
from neutronstarlite_tpu.models.base import ToolkitBase
from neutronstarlite_tpu.models.gcn import (
    GCNTrainer,
    aggregate_input,
    gcn_forward,
    init_gcn_params,
)
from neutronstarlite_tpu.nn.param import adam_init, adam_update
from neutronstarlite_tpu.utils.config import InputInfo

V, F0, HIDDEN, CLASSES = 90, 13, 8, 3  # three distinct widths


def _data(seed=5, e_num=700):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, V, size=e_num, dtype=np.uint32)
    dst = rng.integers(0, V, size=e_num, dtype=np.uint32)
    datum = GNNDatum.random_generate(V, F0, CLASSES, seed=seed)
    return src, dst, datum, build_graph(src, dst, V, weight="gcn_norm")


def _cfg(algo, layers=f"{F0}-{HIDDEN}-{CLASSES}", **kw):
    cfg = InputInfo()
    cfg.algorithm = algo
    cfg.vertices = V
    cfg.layer_string = layers
    cfg.epochs = 3
    cfg.learn_rate = 0.01
    cfg.weight_decay = 1e-4
    cfg.decay_epoch = -1
    cfg.drop_rate = 0.3
    for k, v in kw.items():
        setattr(cfg, k, v)
    return cfg


def _build(algo, data, **kw):
    src, dst, datum, g = data
    return get_algorithm(algo).from_arrays(
        _cfg(algo, **kw), src, dst, datum, host_graph=g
    )


def _device_graph(kind, g):
    if kind == "ell":
        from neutronstarlite_tpu.ops.ell import EllPair

        return EllPair.from_host(g)
    from neutronstarlite_tpu.ops.device_graph import DeviceGraph

    return DeviceGraph.from_host(g, edge_chunk=256)


# ---- (a) the forward with the aggregate handed in ---------------------------


@pytest.mark.parametrize("sublinear", [False, True])
@pytest.mark.parametrize("dtype,tol", [(None, 1e-6), (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("kind", ["scatter", "ell"])
def test_forward_on_the_aggregate_equals_forward_on_the_features(
        kind, dtype, tol, sublinear):
    _, _, datum, g = _data()
    graph = _device_graph(kind, g)
    x = jnp.asarray(datum.feature)
    params = init_gcn_params(
        jax.random.PRNGKey(0), [F0, HIDDEN, HIDDEN, CLASSES]
    )
    key = jax.random.PRNGKey(1)
    target = jnp.asarray(
        np.random.default_rng(0).standard_normal((V, CLASSES)), jnp.float32
    )

    def run(feature, aggregated):
        def loss(p):
            logits = gcn_forward(
                graph, p, feature, key, 0.3, True, compute_dtype=dtype,
                sublinear=sublinear, input_aggregated=aggregated,
            )
            return jnp.sum(logits * target), logits

        (_, logits), grads = jax.jit(
            jax.value_and_grad(loss, has_aux=True)
        )(params)
        return logits, grads

    want_logits, want_grads = run(x, False)
    agg = jax.jit(aggregate_input, static_argnums=2)(graph, x, dtype)
    assert agg.shape == x.shape and agg.dtype == (dtype or x.dtype)
    got_logits, got_grads = run(agg, True)
    scale = float(jnp.max(jnp.abs(want_logits)))
    np.testing.assert_allclose(
        got_logits, want_logits, rtol=tol, atol=tol * scale
    )
    for got, want in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(
            got, want, rtol=tol, atol=tol * float(jnp.max(jnp.abs(want)))
        )


def test_the_eager_order_has_no_aggregate_to_take():
    _, _, datum, g = _data()
    params = init_gcn_params(jax.random.PRNGKey(0), [F0, HIDDEN, CLASSES])
    with pytest.raises(ValueError, match="eager"):
        gcn_forward(
            _device_graph("ell", g), params, jnp.asarray(datum.feature),
            jax.random.PRNGKey(0), 0.0, False, eager=True,
            input_aggregated=True,
        )


def test_host_cast_of_the_features_is_the_device_cast():
    """bfloat16 features go up already cast: numpy's cast must give the
    bits XLA's gives from the float32 table (round to nearest even)."""
    rng = np.random.default_rng(3)
    x = rng.standard_normal((512, 37)).astype(np.float32)
    x[0, :8] = [0.0, -0.0, np.inf, -np.inf, 3.3895314e38, 1e-30, -1e-38, 1e-40]
    # ties: exactly half a bfloat16 ulp above an even and an odd mantissa
    x[1, :2] = np.array([0x3F808000, 0x3F818000], np.uint32).view(np.float32)
    host = x.astype(jnp.bfloat16)
    device = np.asarray(jnp.asarray(x).astype(jnp.bfloat16))
    np.testing.assert_array_equal(host.view(np.uint16), device.view(np.uint16))


# ---- (b) the trainer's trajectory -------------------------------------------


@pytest.mark.parametrize("optim_kernel", [False, True])
def test_trainer_reproduces_the_unhoisted_loss_trajectory(optim_kernel):
    data = _data()
    trainer = _build("GCNCPU", data, optim_kernel=optim_kernel)
    assert trainer.input_hoisted
    params, graph = trainer.params, trainer.compute_graph
    opt_state = adam_init(params)
    feature = jnp.asarray(data[2].feature)
    label, train01 = trainer.label, trainer._train_mask01
    drop, adam_cfg = trainer.cfg.drop_rate, trainer.adam_cfg

    @jax.jit
    def step(params, opt_state, key):
        def loss_fn(p):  # the un-hoisted forward, on the raw features
            logits = gcn_forward(graph, p, feature, key, drop, True)
            return ToolkitBase.masked_nll_loss(logits, label, train01)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        return adam_update(params, grads, opt_state, adam_cfg) + (loss,)

    want = []
    key = jax.random.PRNGKey(trainer.seed + 1)
    for epoch in range(trainer.cfg.epochs):
        params, opt_state, loss = step(
            params, opt_state, jax.random.fold_in(key, epoch)
        )
        want.append(float(loss))
    trainer.run()
    np.testing.assert_allclose(trainer.loss_history, want, rtol=1e-5, atol=1e-6)


# ---- (c) what the step program holds ----------------------------------------


def _sub_jaxprs(value):
    if hasattr(value, "eqns"):
        yield value
    elif hasattr(value, "jaxpr"):
        yield from _sub_jaxprs(value.jaxpr)
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _sub_jaxprs(v)


def _gather_operand_widths(jaxpr):
    """Last dimension of the operand of every ``gather`` in the jaxpr and
    everything nested in it (scan bodies, custom_vjp rules, pjit calls)."""
    widths = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            widths.append(eqn.invars[0].aval.shape[-1])
        for value in eqn.params.values():
            for sub in _sub_jaxprs(value):
                widths += _gather_operand_widths(sub)
    return widths


@pytest.mark.parametrize("layers,n_layers", [
    (f"{F0}-{HIDDEN}-{CLASSES}", 2), (f"{F0}-{HIDDEN}-{HIDDEN}-{CLASSES}", 3),
])
def test_step_program_lost_the_input_pass_and_nothing_else(
        monkeypatch, layers, n_layers):
    from neutronstarlite_tpu.ops import ell

    trainer = _build("GCNCPU", _data(), layers=layers, optim_kernel=True)
    assert trainer.metrics.snapshot()["gauges"]["agg.input_hoisted"] == 1
    assert trainer.feature.shape == (V, F0)

    calls = []  # widths of the passes traced, forward and backward
    inner = ell.ell_tables_aggregate

    def counted(x, *a, **kw):
        calls.append(int(x.shape[1]))
        return inner(x, *a, **kw)

    monkeypatch.setattr(ell, "ell_tables_aggregate", counted)
    jaxpr = jax.make_jaxpr(trainer._train_step)(*trainer.aot_args())
    assert len(calls) == 2 * (n_layers - 1)
    assert F0 not in calls
    widths = _gather_operand_widths(jaxpr.jaxpr)
    assert HIDDEN in widths and F0 not in widths
    # and with the hook at its default the pass is there: the counter counts
    monkeypatch.setattr(GCNTrainer, "hoists_input_aggregate", lambda self: False)
    plain = _build("GCNCPU", _data(), layers=layers, optim_kernel=True)
    assert plain.metrics.snapshot()["gauges"]["agg.input_hoisted"] == 0
    calls.clear()
    jaxpr = jax.make_jaxpr(plain._train_step)(*plain.aot_args())
    assert len(calls) == 2 * n_layers - 1 and F0 in calls  # no grad into x
    assert F0 in _gather_operand_widths(jaxpr.jaxpr)


# ---- (d) everybody else keeps their programs --------------------------------

CONTROLS = [
    ("GCNEAGER", {}), ("GINCPU", {}), ("COMMNETCPU", {}), ("GATCPU", {}),
    ("GINDIST", {"partitions": 2, "dist_path": "ring_blocked_sim", "kernel_tile": 16}),
    ("COMMNETDIST", {"partitions": 2, "dist_path": "ring_blocked_sim", "kernel_tile": 16}),
    ("GCNEAGERDIST", {"partitions": 2, "dist_path": "ring_blocked_sim", "kernel_tile": 16}),
]


@pytest.mark.parametrize("algo,kw", CONTROLS, ids=[c[0] for c in CONTROLS])
def test_other_trainers_do_not_hoist_and_keep_their_step(monkeypatch, algo, kw):
    def step_text(trainer):
        assert trainer.metrics.snapshot()["gauges"]["agg.input_hoisted"] == 0
        assert not trainer.input_hoisted
        return trainer._train_step.lower(*trainer.aot_args()).as_text()

    data = _data()
    as_shipped = _build(algo, data, **kw)
    assert not as_shipped.hoists_input_aggregate()
    assert "input_aggregate" not in as_shipped.timers.snapshot()
    text = step_text(as_shipped)
    monkeypatch.setattr(
        type(as_shipped), "hoists_input_aggregate",
        ToolkitBase.hoists_input_aggregate,
    )
    assert step_text(_build(algo, data, **kw)) == text


def test_a_subclass_that_changes_the_forward_does_not_inherit_the_yes():
    from neutronstarlite_tpu.models.gcn_dist import DistGCNTrainer, gcn_layer_nn

    class OtherForward(GCNTrainer):
        def model_forward(self, params, graph, x, key, train):
            return super().model_forward(params, graph, x * 2.0, key, train)

    class OtherLayerNN(DistGCNTrainer):
        layer_nn = staticmethod(
            lambda i, n, layer, agg, x_in, *a, **kw:
            gcn_layer_nn(i, n, layer, agg + x_in, x_in, *a, **kw)
        )

    data = _data()
    src, dst, datum, g = data
    t = OtherForward.from_arrays(_cfg("GCNCPU"), src, dst, datum, host_graph=g)
    assert not t.input_hoisted
    np.testing.assert_array_equal(np.asarray(t.feature), datum.feature)
    d = OtherLayerNN.from_arrays(
        _cfg("GCNDIST", partitions=2, dist_path="ring_blocked_sim",
             kernel_tile=16), src, dst, datum, host_graph=g,
    )
    assert not d.input_hoisted
    np.testing.assert_array_equal(
        np.asarray(d.feature_p), d.dist.pad_vertex_array(datum.feature)
    )


# ---- (e) dist ---------------------------------------------------------------

DIST_PATHS = {
    "ell": {"optim_kernel": True},
    "ring": {"comm_layer": "ring"},
    "ring_blocked": {"dist_path": "ring_blocked", "kernel_tile": 16},
}


@pytest.mark.parametrize("path", sorted(DIST_PATHS))
@pytest.mark.parametrize("precision", ["float32", "bfloat16"])
def test_dist_hoisted_logits_equal_unhoisted(monkeypatch, path, precision):
    from neutronstarlite_tpu.models.gcn_dist import DistGCNTrainer

    data = _data()
    kw = dict(DIST_PATHS[path], partitions=2, precision=precision)
    hoisted = _build("GCNDIST", data, **kw)
    assert hoisted.input_hoisted
    assert hoisted.metrics.snapshot()["gauges"]["agg.input_hoisted"] == 1
    monkeypatch.setattr(DistGCNTrainer, "hoists_input_aggregate", lambda self: False)
    plain = _build("GCNDIST", data, **kw)
    assert not plain.input_hoisted

    assert hoisted.feature_p.shape == plain.feature_p.shape
    assert hoisted.feature_p.sharding == plain.feature_p.sharding
    assert hoisted.feature_p.dtype == (
        jnp.bfloat16 if precision == "bfloat16" else jnp.float32
    )
    key = jax.random.PRNGKey(0)

    def logits(t):
        return np.asarray(t._eval_logits(
            t.params, t.blocks, t.feature_p, t.valid_p, key
        ))

    want = logits(plain)
    tol = 1e-6 if precision == "float32" else 2e-2
    np.testing.assert_allclose(
        logits(hoisted), want, rtol=tol, atol=tol * np.abs(want).max()
    )
    # the epoch prices the hidden width; the input width is priced once
    rows = hoisted.metrics.snapshot()["gauges"]["wire.rows_per_layer"]
    item = 2 if precision == "bfloat16" else 4
    gauges = hoisted.metrics.snapshot()["gauges"]
    assert gauges["wire.bytes_per_epoch_fwd"] == rows * HIDDEN * item
    assert gauges["wire.bytes_input_aggregate"] == rows * F0 * item
    plain_gauges = plain.metrics.snapshot()["gauges"]
    assert plain_gauges["wire.bytes_per_epoch_fwd"] == rows * (F0 + HIDDEN) * item
    assert plain_gauges["wire.bytes_input_aggregate"] == 0


def test_dist_trainer_trajectory_and_rebuild(monkeypatch):
    """Three epochs on the sim twin equal the un-hoisted trainer's, and a
    second build_model (what an elastic replan and a from-scratch restart
    call) rebuilds the aggregate from the datum in the same place."""
    from neutronstarlite_tpu.models.gcn_dist import DistGCNTrainer

    data = _data()
    kw = dict(partitions=2, dist_path="ring_blocked_sim", kernel_tile=16)
    hoisted = _build("GCNDIST", data, **kw)
    first = np.asarray(hoisted.feature_p)
    hoisted.cfg.partitions = 3  # a survivor replan re-enters build_model
    hoisted.build_model()
    assert hoisted.dist.partitions == 3 and hoisted.input_hoisted
    assert hoisted.timers.snapshot()["input_aggregate"]["count"] == 2
    np.testing.assert_allclose(
        hoisted.dist.unpad_vertex_array(np.asarray(hoisted.feature_p)),
        DistGCNTrainer.from_arrays(
            _cfg("GCNDIST", **kw), *data[:3], host_graph=data[3]
        ).dist.unpad_vertex_array(first),
        rtol=1e-5, atol=1e-6,
    )
    hoisted.run()
    monkeypatch.setattr(DistGCNTrainer, "hoists_input_aggregate", lambda self: False)
    plain = _build("GCNDIST", data, **dict(kw, partitions=3))
    plain.run()
    np.testing.assert_allclose(
        hoisted.loss_history, plain.loss_history, rtol=1e-5, atol=1e-6
    )


# ---- (f) the float32 table does not stay ------------------------------------


def _live_arrays(shape, dtype):
    return [
        a for a in jax.live_arrays()
        if a.shape == shape and a.dtype == dtype and not a.is_deleted()
    ]


@pytest.mark.parametrize("algo,kw", [
    ("GCNCPU", {"optim_kernel": True}),
    ("GCNDIST", {"partitions": 2, "optim_kernel": True}),
])
def test_no_float32_feature_table_on_the_device_under_bfloat16(algo, kw):
    import gc

    # a width no other test's arrays have, so the census is this trainer's
    f0 = 29
    rng = np.random.default_rng(8)
    src = rng.integers(0, V, size=600, dtype=np.uint32)
    dst = rng.integers(0, V, size=600, dtype=np.uint32)
    datum = GNNDatum.random_generate(V, f0, CLASSES, seed=8)
    cfg = _cfg(algo, layers=f"{f0}-{HIDDEN}-{CLASSES}", precision="bfloat16", **kw)
    trainer = get_algorithm(algo).from_arrays(cfg, src, dst, datum)
    gc.collect()
    table = trainer.feature if algo == "GCNCPU" else trainer.feature_p
    assert table.dtype == jnp.bfloat16 and table.shape[1] == f0
    assert _live_arrays(table.shape, jnp.float32) == []
    assert len(_live_arrays(table.shape, jnp.bfloat16)) == 1  # the aggregate
    assert trainer.datum.feature.dtype == np.float32  # the host copy stays


# ---- (g) serving reads the raw rows -----------------------------------------


class _ServableGCN(GCNTrainer):
    with_bn = False  # the engine serves layers of one dense W each


def test_engine_over_a_fullbatch_gcn_predicts_from_the_raw_features(
        monkeypatch, tmp_path):
    from neutronstarlite_tpu.serve.engine import InferenceEngine

    src, dst, datum, g = _data()
    cfg = _cfg("GCNCPU", fanout_string="3-3", batch_size=16,
               checkpoint_dir=str(tmp_path / "ckpt"))
    toolkit = _ServableGCN.from_arrays(cfg, src, dst, datum, host_graph=g)
    toolkit.run()
    assert toolkit.input_hoisted
    ids = np.array([1, 5, 9, 40])

    def predict(tk):
        engine = InferenceEngine(tk, cfg.checkpoint_dir,
                                 rng=np.random.default_rng(0))
        np.testing.assert_array_equal(np.asarray(engine.feature), datum.feature)
        return engine.predict(ids)

    got = predict(toolkit)
    assert toolkit.feature is not toolkit.raw_feature
    assert not np.array_equal(np.asarray(toolkit.feature), datum.feature)
    # the parent's toolkit: the same class with the hook at its default,
    # whose ``feature`` is the uploaded table
    monkeypatch.setattr(GCNTrainer, "hoists_input_aggregate", lambda self: False)
    parent = _ServableGCN.from_arrays(cfg, src, dst, datum, host_graph=g)
    assert parent.raw_feature is parent.feature
    np.testing.assert_array_equal(got, predict(parent))
    # a grown slab written through the accessor leaves the aggregate alone
    aggregate = toolkit.feature
    toolkit.raw_feature = jnp.zeros((V + 4, F0), jnp.float32)
    assert toolkit.feature is aggregate
    assert toolkit.raw_feature.shape == (V + 4, F0)
    parent.raw_feature = jnp.zeros((V + 4, F0), jnp.float32)
    assert parent.feature.shape == (V + 4, F0)


# ---- (h) how far the level tables are padded --------------------------------


def _benchmark_generator():
    """benchmark/harness/data.py:power_law_edges, by its path (the
    benchmark is no package of the program's)."""
    import importlib.util
    import os

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark", "harness", "data.py",
    )
    spec = importlib.util.spec_from_file_location("_bench_data", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.power_law_edges


# a fiftieth of each benchmark configuration's graph (seed 7, exponent 3),
# through the trainer that its cell runs. Measured (my CPU counts, PR 29):
# 1.196 on one device (the power-of-two ladder: 1.464) and 1.468 stacked
# over four (1.862). At this size the largest hub (degree 67,801) is a
# tenth of a device's edges, every device pads to it, and its level keeps
# the ladder's width of 131,072 (ops/ell._POW2_WIDTHS_ABOVE), so the stacked
# bound is 1.55 here where the full-size tables read 1.25.
@pytest.mark.parametrize("algo,kw,vertices,edges,symmetric,limit", [
    ("GCNCPU", {}, 232965 // 50, 114615892 // 50, False, 1.25),
    ("GCNDIST", {"partitions": 4}, 2449029 // 50,
     (126167309 - 2449029) // 100 * 2 + 2449029 // 50, True, 1.55),
], ids=["reddit_fiftieth_one_device", "products_fiftieth_stacked_over_four"])
def test_slots_per_edge_counter_on_the_benchmark_graphs(
    algo, kw, vertices, edges, symmetric, limit
):
    from neutronstarlite_tpu.obs import flight
    from neutronstarlite_tpu.ops.ell import MAX_LEVELS

    src, dst = _benchmark_generator()(
        vertices, edges, 7, exponent=3.0, self_loops=True, symmetric=symmetric
    )
    g = build_graph(src, dst, vertices, weight="gcn_norm")
    datum = GNNDatum.random_generate(vertices, F0, CLASSES, seed=1)
    cfg = _cfg(algo, optim_kernel=True, **kw)
    cfg.vertices = vertices
    trainer = get_algorithm(algo).from_arrays(cfg, src, dst, datum, host_graph=g)
    gauges = trainer.metrics.snapshot()["gauges"]
    assert 1.0 <= gauges["agg.slots_per_edge"] < limit, gauges
    assert 1 <= gauges["agg.levels"] <= MAX_LEVELS
    span = [r for r in flight.recent_records("span") if r["name"] == "tables_stats"][-1]
    assert span["cat"] == "phase" and span["edges"] == 2 * g.e_num
    assert span["levels"] == gauges["agg.levels"]
    assert span["slots"] / span["edges"] == gauges["agg.slots_per_edge"]
    tables = trainer.compute_graph if algo == "GCNCPU" else trainer.blocks
    assert span["slots"] == sum(
        int(np.prod(n.shape)) for side in (tables.fwd, tables.bwd) for n in side.nbr
    )
