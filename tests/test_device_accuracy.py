"""The one-chip full-batch loop counts its accuracies on the device (ISSUE 32).

``base._split_counts`` (one jitted program) against the numpy arithmetic the
loop ran on the host until PR 32; the run loop's log lines, ``acc``
dictionary, counters and spans; and that no ``[V, classes]`` array is turned
into a numpy one while ``run()`` trains.
"""

import logging
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neutronstarlite_tpu.models import base

V, CLASSES = 600, 7
SPLITS = ("Train", "Eval", "Test")


def host_count(logits, label, mask, which):
    """(n, correct) as ``ToolkitBase.test`` counted them in numpy."""
    sel = mask == which
    n = int(sel.sum())
    if n == 0:
        return 0, 0
    return n, int((logits[sel].argmax(axis=1) == label[sel]).sum())


def host_lines(logits, label, mask):
    """The lines the host count logged, and its ``acc`` dictionary: no line
    for a split with no vertex, accuracy 0.0 there."""
    lines, accs = [], {}
    for which, name in enumerate(SPLITS):
        n, c = host_count(logits, label, mask, which)
        accs[name.lower()] = c / n if n else 0.0
        if n:
            lines.append("%s Acc: %f %d %d" % (name, c / n, n, c))
    return lines, accs


def _case(name):
    rng = np.random.default_rng(7)
    logits = rng.standard_normal((V, CLASSES)).astype(np.float32)
    label = rng.integers(0, CLASSES, size=V).astype(np.int32)
    mask = (np.arange(V) % 3).astype(np.int32)
    if name == "ties":  # the largest value twice, the label on either
        rows = np.arange(0, V, 2)
        first = rng.integers(0, CLASSES - 1, size=rows.size)
        second = first + 1 + rng.integers(0, CLASSES - 1 - first)
        logits[rows, first] = 9.0
        logits[rows, second] = 9.0
        label[rows] = np.where(np.arange(rows.size) % 2 == 0, first, second)
    elif name == "all_equal_rows":
        logits[::5] = 0.25
        label[::10] = 0
    elif name == "nan_rows":  # numpy's argmax takes the first NaN
        logits[::4, 3] = np.nan
        logits[::8, 5] = np.nan
        label[::4] = np.where(np.arange(0, V, 4) % 3 == 0, 3, 5)
    elif name == "empty_split":
        mask[mask == 1] = 2
    elif name == "in_no_split":  # OGB's 3: a vertex of no split
        mask[::7] = 3
    else:
        assert name == "random"
    return logits, label, mask


CASES = ["random", "ties", "all_equal_rows", "nan_rows", "empty_split", "in_no_split"]


@pytest.mark.parametrize("which", [0, 1, 2], ids=SPLITS)
@pytest.mark.parametrize("case", CASES)
def test_device_counts_equal_the_host_counts(case, which):
    logits, label, mask = _case(case)
    correct, total = base._split_counts(
        jnp.asarray(logits), jnp.asarray(label), jnp.asarray(mask)
    )
    n, c = host_count(logits, label, mask, which)
    assert (int(total[which]), int(correct[which])) == (n, c)
    if case == "empty_split" and which == 1:
        assert n == 0


@pytest.mark.parametrize("case", ["random", "ties"])
def test_padding_rows_are_left_out_as_the_sharded_report_needs(case):
    logits, label, mask = _case(case)
    valid = (np.arange(V) % 4 != 3).astype(np.int32)
    correct, total = base._split_counts(
        jnp.asarray(logits), jnp.asarray(label), jnp.asarray(mask), jnp.asarray(valid)
    )
    keep = valid > 0
    for which in range(3):
        assert (int(total[which]), int(correct[which])) == host_count(
            logits[keep], label[keep], mask[keep], which
        )


# ---- the run loop

FAMILIES = {
    "gcn_ell": ("GCNCPU", True), "gcn": ("GCNCPU", False),
    "gcn_eager": ("GCNCPUEAGER", False), "gin": ("GINCPU", False),
    "commnet": ("COMMNETGPU", False), "gat": ("GATCPU", False),
    "ggcn": ("GGCNCPU", False),
}


def _trainer(family, epochs, mask=None):
    from neutronstarlite_tpu.graph.dataset import GNNDatum
    from neutronstarlite_tpu.graph.storage import build_graph
    from neutronstarlite_tpu.graph.synthetic import planted_partition_graph
    from neutronstarlite_tpu.utils.config import InputInfo

    algorithm, optim_kernel = FAMILIES[family]
    v_num, f, classes = 2708, 48, CLASSES  # Cora's vertices
    src, dst, feature, label = planted_partition_graph(
        v_num, classes, avg_degree=5, feature_size=f, seed=11
    )
    if mask is None:
        mask = (np.arange(v_num) % 3).astype(np.int32)
    datum = GNNDatum(feature=feature, label=label.astype(np.int32), mask=mask)
    cfg = InputInfo()
    cfg.algorithm = algorithm
    cfg.vertices = v_num
    cfg.layer_string = f"{f}-16-{classes}"
    cfg.epochs = epochs
    cfg.learn_rate = 0.01
    cfg.decay_epoch = -1
    cfg.drop_rate = 0.3
    cfg.optim_kernel = optim_kernel
    cls = base.get_algorithm(algorithm)
    host_graph = build_graph(src, dst, v_num, weight=cls.weight_mode)
    return cls.from_arrays(cfg, None, None, datum, seed=0, host_graph=host_graph)


class _Lines(logging.Handler):
    def __init__(self):
        super().__init__()
        self.lines = []

    def emit(self, record):
        self.lines.append(record.getMessage())


@pytest.fixture
def nts_log():
    handler, logger = _Lines(), logging.getLogger("nts")
    logger.addHandler(handler)
    yield handler.lines
    logger.removeHandler(handler)


def _spy_step_logits(trainer):
    """Host copies of the logits every train step returned, taken by the
    test (the loop itself copies none)."""
    seen, inner = [], trainer._train_step

    def step(*args):
        out = inner(*args)
        seen.append(np.asarray(out[3]))
        return out

    trainer._train_step = step
    return seen


ACC_LINE = re.compile(r"(Train|Eval|Test) Acc: ")


def _acc_lines(lines):
    return [m for m in lines if ACC_LINE.match(m)]


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_run_logs_and_returns_what_the_host_count_gives(family, nts_log, monkeypatch):
    monkeypatch.delenv("NTS_TRACE_STEP", raising=False)
    monkeypatch.delenv("NTS_FINAL_EVAL", raising=False)
    epochs = 3
    trainer = _trainer(family, epochs)
    seen = _spy_step_logits(trainer)
    del nts_log[:]
    result = trainer.run()
    label, mask = trainer.datum.label, trainer.datum.mask

    expected = []
    for epoch, logits in enumerate(seen):
        expected += host_lines(logits, label, mask)[0]
        expected.append("Epoch %d loss %f" % (epoch, trainer.loss_history[epoch]))
    final = np.asarray(trainer._eval_logits(
        trainer.params, trainer.compute_graph, trainer.feature, jax.random.PRNGKey(1)
    ))
    final_lines, final_accs = host_lines(final, label, mask)
    expected += final_lines
    got = [m for m in nts_log if ACC_LINE.match(m) or m.startswith("Epoch ")]
    assert len(seen) == epochs and got == expected
    assert result["acc"] == final_accs
    assert trainer.metrics.counter_get("acc.device_counts") == epochs
    assert trainer.metrics.counter_get("acc.host_bytes") == 0


def test_a_split_with_no_vertex_logs_no_line_and_reads_zero(nts_log):
    mask = (np.arange(2708) % 2 * 2).astype(np.int32)  # no Eval vertex
    trainer = _trainer("gcn_ell", 1, mask=mask)
    del nts_log[:]
    result = trainer.run()
    names = [m.split()[0] for m in _acc_lines(nts_log)]
    assert names == ["Train", "Test"] * 2  # the cadence epoch, the final eval
    assert result["acc"]["eval"] == 0.0 and result["acc"]["train"] > 0.0


class _NumpySpy:
    """numpy, with the shapes of what ``asarray`` / ``array`` were given.
    (On the CPU a jax array becomes a numpy one through the buffer protocol,
    past every hook of jax's own, so the test watches the modules' ``np``.)"""

    def __init__(self, seen):
        self._seen = seen

    def __getattr__(self, name):
        return getattr(np, name)

    def asarray(self, a, *args, **kwargs):
        self._seen.append(np.shape(a))
        return np.asarray(a, *args, **kwargs)

    def array(self, a, *args, **kwargs):
        self._seen.append(np.shape(a))
        return np.array(a, *args, **kwargs)


@pytest.mark.parametrize("epochs", [2, 45])
def test_no_logits_reach_the_host_over_the_epoch_loop(epochs, monkeypatch):
    from neutronstarlite_tpu.models import fullbatch

    trainer = _trainer("gcn_ell", epochs)
    v_num = trainer.datum.label.shape[0]
    to_host, device_get = [], jax.device_get

    def spy_get(tree):
        to_host.extend(np.shape(leaf) for leaf in jax.tree.leaves(tree))
        return device_get(tree)

    monkeypatch.setattr(base, "np", _NumpySpy(to_host))
    monkeypatch.setattr(fullbatch, "np", _NumpySpy(to_host))
    monkeypatch.setattr(jax, "device_get", spy_get)
    trainer.run()
    monkeypatch.undo()

    assert (3,) in to_host  # the counts came this way: the spy sees the path
    assert not [s for s in to_host if s and s[0] == v_num]
    cadence = [e for e in range(epochs)
               if e % max(1, epochs // 20) == 0 or e == epochs - 1]
    assert len(cadence) == {2: 2, 45: 23}[epochs]
    assert trainer.metrics.counter_get("acc.device_counts") == len(cadence)
    assert trainer.metrics.counter_get("acc.host_bytes") == 0
    spans = trainer.metrics.flight.records("span")
    epoch_ids = {s["span_id"]: s["epoch"] for s in spans if s["name"] == "epoch"}
    staged = [(s["name"], epoch_ids[s["parent_id"]]) for s in spans
              if s["parent_id"] in epoch_ids]
    assert "logits_copy" not in {name for name, _ in staged}
    for name in ("accuracy_dispatch", "host_accuracy"):
        assert [e for n, e in staged if n == name] == cadence
    summary = trainer.run_summary_record
    assert summary["counters"]["acc.device_counts"] == len(cadence)
    assert summary["counters"]["acc.host_bytes"] == 0


def test_the_split_step_mode_logs_the_loss_and_no_accuracy(nts_log, monkeypatch):
    monkeypatch.setenv("NTS_TRACE_STEP", "1")
    monkeypatch.setenv("NTS_FINAL_EVAL", "0")  # whose lines are not the loop's
    trainer = _trainer("gcn_ell", 2)
    del nts_log[:]
    result = trainer.run()
    assert [m for m in nts_log if m.startswith("Epoch ")] == [
        "Epoch %d loss %f" % (e, trainer.loss_history[e]) for e in range(2)
    ]
    assert _acc_lines(nts_log) == []
    assert trainer.metrics.counter_get("acc.device_counts") == 0
    assert result["acc"] == {"train": None, "eval": None, "test": None}
    names = {s["name"] for s in trainer.metrics.flight.records("span")}
    assert {"forward_backward", "optim"} <= names
    assert not {"accuracy_dispatch", "host_accuracy"} & names
