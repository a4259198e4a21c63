"""serve/crosshost: the cross-host serve fabric, socket-free.

Contract under test: the router generalizes PR 14's routing over
SCRAPED state (route-state derivation from /telemetry records), owed
requests re-route instead of dropping when a replica dies, supervised
restart respawns from the recorded launch recipe, and the rolling
rollout state machine holds its invariants under races — ``close()``
mid-rollout, a replica killed between drain and restart, a concurrent
second rollout — never leaking a process and never dropping an owed
request. The rollout preflight refuses a digest-corrupt candidate with
ZERO replicas restarted (satellite: tools/verify_checkpoint as the
promotion gate).

The rig fakes the PROCESS layer (spawn/port-file/HTTP) while running
the real router, hub, dispatch, and rollout code: ``_spawn_child`` is
monkeypatched to a registry of fake procs that publish real port files,
and ``httpc.fetch`` is monkeypatched to an in-memory transport serving
schema-valid /telemetry payloads and /predict answers keyed by each
fake replica's checkpoint — so "which model answered" is observable.
The real-process path is exercised end-to-end by the CROSSHOST_GATE in
scripts/ci_tier1.sh.
"""

from __future__ import annotations

import json
import os
import threading
import time
import zlib

import numpy as np
import pytest

from neutronstarlite_tpu.obs import registry, schema
from neutronstarlite_tpu.obs.httpc import HttpRefused
from neutronstarlite_tpu.serve import crosshost
from neutronstarlite_tpu.serve.batcher import RequestShedError


def _ckpt_tag(ckpt_dir: str) -> float:
    """What a fake replica answers with: names the checkpoint it serves.
    A wide modulus (exact in float32): two checkpoints under ``% 97`` of a
    salted ``hash`` collided in about one suite run in a hundred."""
    return float(zlib.crc32(ckpt_dir.encode()) % 65521)


# ---- rig: fake processes + in-memory transport -----------------------------


class FakeProc:
    _pids = iter(range(50000, 60000))

    def __init__(self, recipe):
        self.recipe = recipe
        self.pid = next(FakeProc._pids)
        self._rc = None

    def poll(self):
        return self._rc

    def terminate(self):
        self._rc = 0

    def kill(self):
        self._rc = -9

    def wait(self, timeout=None):
        return self._rc


class FakeWorld:
    """The process table + network: port -> fake replica process."""

    def __init__(self):
        self.ports = {}
        self.next_port = 41000
        self.spawns = 0
        self.fail_next_spawn = False
        self.breaching = set()  # ports reporting a breaching serve SLO
        self.seq = 0
        self.lock = threading.Lock()

    def spawn(self, recipe):
        with self.lock:
            if self.fail_next_spawn:
                self.fail_next_spawn = False
                raise RuntimeError("injected spawn failure")
            self.spawns += 1
            port = self.next_port
            self.next_port += 1
            proc = FakeProc(recipe)
            self.ports[port] = proc
        crosshost._write_port_file(recipe.port_file, {
            "port": port, "pid": proc.pid, "replica": recipe.replica,
        })
        return proc

    def alive(self):
        return [p for p in self.ports.values() if p.poll() is None]

    def proc_at(self, base_url):
        return self.ports.get(int(base_url.rsplit(":", 1)[1]))

    def _record(self, kind, run_id, **fields):
        with self.lock:
            self.seq += 1
            seq = self.seq
        rec = {"event": kind, "ts": time.time(), "run_id": run_id,
               "schema": schema.SCHEMA_VERSION, "seq": seq, **fields}
        schema.validate_event(rec)  # the fake must speak real schema
        return json.dumps(rec)

    def telemetry(self, port, proc):
        rid = proc.recipe.replica
        lines = [self._record(
            "telemetry", f"{rid}-run", source="serve", replica=rid,
            counters={}, gauges={"serve.queue_depth": 0,
                                 "serve.max_queue": 64},
            health={"ok": True, "serve": {"beating": True}},
        )]
        if port in self.breaching:
            lines.append(self._record(
                "slo_status", f"{rid}-run",
                objective="serve_p99_ms<=5@1m", metric="serve_p99_ms",
                state="breach", threshold=5.0, window_s=60.0, value=50.0,
                burn_rate=10.0, burn_rate_short=10.0, window_count=10,
            ))
        return "\n".join(lines) + "\n"

    def predict(self, port, proc, payload):
        ids = payload["node_ids"]
        tag = _ckpt_tag(proc.recipe.ckpt_dir)
        return json.dumps({
            "status": "ok", "dtype": "float32",
            "values": [[tag + float(i)] for i in ids],
            "replica": proc.recipe.replica,
        })

    def fetch(self, url, **kw):
        rest = url.split("://", 1)[1]
        hostport, _, path = rest.partition("/")
        port = int(hostport.rsplit(":", 1)[1])
        proc = self.ports.get(port)
        if proc is None or proc.poll() is not None:
            raise HttpRefused(f"nothing listening on {url}")
        if path.startswith("telemetry"):
            return self.telemetry(port, proc)
        if path.startswith("predict"):
            return self.predict(port, proc, json.loads(kw["data"]))
        raise HttpRefused(f"unknown path {url}")


@pytest.fixture()
def world(monkeypatch):
    w = FakeWorld()
    monkeypatch.setattr(crosshost, "_spawn_child", w.spawn)
    monkeypatch.setattr(crosshost.httpc, "fetch", w.fetch)
    yield w


def _mk_fleet(world, tmp_path, n=2, *, polling=False, **kw):
    cfg = tmp_path / "fake.cfg"
    if not cfg.exists():
        cfg.write_text("ALGORITHM:FAKE\n")
    reg = registry.MetricsRegistry(
        "router-none-0", algorithm="ROUTER", fingerprint="f",
        path=str(tmp_path / "router.jsonl"),
    )
    fleet = crosshost.CrossHostFleet.spawn(
        str(cfg), str(tmp_path / "ckpt_v1"), n,
        spawn_dir=str(tmp_path / "spawn"), registry=reg,
        poll_s=0.05, miss_k=2, predict_timeout_s=5.0,
        spawn_timeout_s=5.0, drain_timeout_s=1.0,
        start_polling=polling, **kw,
    )
    return fleet, reg


def _records(reg, tmp_path, kind=None):
    reg.close()
    out = [json.loads(ln) for ln in open(tmp_path / "router.jsonl")
           if ln.strip()]
    return [e for e in out if kind is None or e["event"] == kind]


def _pass_canary(fleet):
    fleet._canary = lambda ckpt: {
        "disagreement": 0.0, "tolerance": 0.05, "seeds": 8,
        "batches": 2, "mirrored": False, "passed": True,
    }


def _pass_preflight(monkeypatch):
    from neutronstarlite_tpu.tools import verify_checkpoint as vc

    monkeypatch.setattr(vc, "preflight_checkpoint",
                        lambda root: (root, 7))


# ---- construction + routing over scraped state -----------------------------


def test_spawn_builds_recipes_and_routes(world, tmp_path):
    fleet, reg = _mk_fleet(world, tmp_path, n=3)
    try:
        assert world.spawns == 3
        assert all(r.recipe is not None for r in fleet.replicas)
        states = fleet.route_states()
        assert [s["beating"] for s in states] == [True] * 3
        v = fleet.predict([1, 2, 3])
        assert v.shape == (3, 1)
    finally:
        fleet.close()
    assert world.alive() == []  # close reaps every child


def test_metric_sheddable_rule():
    assert crosshost._metric_sheddable("serve_p99_ms")
    assert crosshost._metric_sheddable("queue_p95_ms")
    assert not crosshost._metric_sheddable("epoch_p99_ms")
    assert not crosshost._metric_sheddable("latency")
    assert not crosshost._metric_sheddable("")


def test_route_state_sees_breach_and_drains(world, tmp_path):
    fleet, reg = _mk_fleet(world, tmp_path, n=2)
    try:
        port0 = int(fleet.replicas[0].base_url.rsplit(":", 1)[1])
        world.breaching.add(port0)
        fleet.hub.poll_once()
        s0, s1 = fleet.route_states()
        assert s0["draining"] and s0["burn"] == 10.0
        assert not s1["draining"]
        # routing avoids the breaching replica
        for _ in range(4):
            v = fleet.predict([5])
            assert v[0, 0] == pytest.approx(
                _ckpt_tag(fleet.replicas[1].ckpt_dir) + 5.0
            )
    finally:
        fleet.close()


def test_fleet_breach_sheds_only_when_all_live_breach(world, tmp_path):
    fleet, reg = _mk_fleet(world, tmp_path, n=2)
    try:
        for r in fleet.replicas:
            world.breaching.add(int(r.base_url.rsplit(":", 1)[1]))
        fleet.hub.poll_once()
        req = fleet.submit([1])
        with pytest.raises(RequestShedError, match="fleet_breach"):
            req.result(timeout=5.0)
    finally:
        fleet.close()
    events = _records(reg, tmp_path, "shed")
    assert len(events) == 1 and "fleet_breach" in events[0]["reason"]


def test_replica_death_reroutes_owed_requests(world, tmp_path):
    """A dead replica's requests re-route to survivors — zero sheds."""
    fleet, reg = _mk_fleet(world, tmp_path, n=2)
    try:
        # prime sticky routing onto r0, then kill it
        for _ in range(3):
            fleet.predict([1])
        world.proc_at(fleet.replicas[0].base_url).kill()
        world.proc_at(fleet.replicas[1].base_url)  # r1 stays up
        results = [fleet.submit([i]) for i in range(8)]
        vals = [r.result(timeout=10.0) for r in results]
        assert all(v is not None for v in vals)
        assert fleet.stats()["shed"] == 0
    finally:
        fleet.close()


def test_submit_after_close_sheds_and_close_is_idempotent(world, tmp_path):
    fleet, reg = _mk_fleet(world, tmp_path, n=2)
    fleet.close()
    req = fleet.submit([1])
    with pytest.raises(RequestShedError):
        req.result(timeout=2.0)
    assert fleet.close() is not None  # second close: no-op, still answers
    assert world.alive() == []


# ---- supervised restart ----------------------------------------------------


def test_miss_k_escalates_to_supervised_restart(world, tmp_path):
    fleet, reg = _mk_fleet(world, tmp_path, n=2, polling=True)
    try:
        victim = fleet.replicas[0]
        old_url = victim.base_url
        world.proc_at(old_url).kill()
        deadline = time.monotonic() + 10.0
        while victim.restarts == 0 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert victim.restarts == 1
        assert victim.base_url != old_url  # re-pointed at the new port
        assert world.proc_at(victim.base_url).poll() is None
        # the respawned replica answers again
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if not fleet.hub.targets[0].lost:
                break
            time.sleep(0.05)
        v = fleet.predict([2])
        assert v is not None
    finally:
        fleet.close()
    events = _records(reg, tmp_path)
    losses = [e for e in events if e["event"] == "target_loss"]
    restarts = [e for e in events if e["event"] == "recovery"
                and e["action"] == "restart"]
    assert len(losses) == 1  # one typed loss per death (latched)
    assert len(restarts) == 1 and restarts[0]["replica"] == "r0"


def test_targets_mode_has_no_recipe_no_restart(world, tmp_path):
    # two already-running "processes"
    reps = [world.spawn(crosshost.LaunchRecipe(
        cfg_path="c", ckpt_dir="k", replica=f"r{i}",
        seed=i, port_file=str(tmp_path / f"t{i}.port"),
    )) for i in range(2)]
    ports = sorted(world.ports)
    reg = registry.MetricsRegistry(
        "router-none-0", algorithm="ROUTER", fingerprint="f",
        path=str(tmp_path / "router.jsonl"),
    )
    fleet = crosshost.CrossHostFleet.from_targets(
        [f"127.0.0.1:{p}" for p in ports], registry=reg,
        poll_s=0.05, miss_k=2, start_polling=False,
    )
    try:
        assert all(r.recipe is None for r in fleet.replicas)
        rec = fleet.rollout(str(tmp_path))
        assert rec["verdict"] == "refused"
        assert "recipe" in rec["error"]
        # a death stays a target_loss: no respawn attempted
        world.ports[ports[0]].kill()
        for _ in range(3):
            fleet.hub.poll_once()
        fleet._supervise()
        assert fleet.hub.targets[0].lost
        assert fleet.replicas[0].restarts == 0
        assert world.spawns == 2  # nothing new spawned
    finally:
        fleet.close()
    # targets mode must NOT kill processes it does not own... but the
    # fake _terminate is real code operating on fake procs the router
    # holds; from_targets never holds procs, so both stay as they were
    assert world.ports[ports[1]].poll() is None


# ---- rollout: preflight + canary gates -------------------------------------


def test_corrupt_checkpoint_rollout_refused(world, tmp_path):
    """Satellite pin: a digest-corrupt candidate is refused by preflight
    with ZERO replicas restarted."""
    import jax.numpy as jnp

    from neutronstarlite_tpu.utils.checkpoint import ARRAYS, save_checkpoint

    ckpt = tmp_path / "cand"
    save_checkpoint(str(ckpt), {"params": [{"W": jnp.arange(8.0)}]}, step=3)
    arrays = next(
        os.path.join(r, f) for r, _d, fs in os.walk(ckpt)
        for f in fs if f == ARRAYS
    )
    size = os.path.getsize(arrays)
    with open(arrays, "r+b") as fh:  # bit-flip a window in the middle
        fh.seek(size // 2)
        window = fh.read(64)
        fh.seek(size // 2)
        fh.write(bytes(b ^ 0xFF for b in window))

    fleet, reg = _mk_fleet(world, tmp_path, n=2)
    try:
        spawns_before = world.spawns
        rec = fleet.rollout(str(ckpt))
        assert rec["verdict"] == "preflight_reject"
        assert rec["restarted"] == 0 and rec["rolled_back"] == 0
        assert world.spawns == spawns_before  # zero replicas touched
        # and a missing checkpoint is refused the same way
        rec2 = fleet.rollout(str(tmp_path / "nonexistent"))
        assert rec2["verdict"] == "preflight_reject"
    finally:
        fleet.close()
    rollouts = _records(reg, tmp_path, "rollout")
    assert [e["verdict"] for e in rollouts] == [
        "preflight_reject", "preflight_reject",
    ]


def test_canary_reject_blocks_rollout(world, tmp_path, monkeypatch):
    _pass_preflight(monkeypatch)
    fleet, reg = _mk_fleet(world, tmp_path, n=2)
    try:
        fleet._canary = lambda ckpt: {
            "disagreement": 0.5, "tolerance": 0.05, "seeds": 8,
            "batches": 2, "mirrored": False, "passed": False,
        }
        spawns_before = world.spawns
        rec = fleet.rollout(str(tmp_path / "cand"))
        assert rec["verdict"] == "canary_reject"
        assert rec["restarted"] == 0
        assert world.spawns == spawns_before
        assert rec["canary"]["disagreement"] == 0.5
    finally:
        fleet.close()


def test_promoted_rollout_restarts_all_and_repins_recipes(
        world, tmp_path, monkeypatch):
    _pass_preflight(monkeypatch)
    fleet, reg = _mk_fleet(world, tmp_path, n=3)
    try:
        _pass_canary(fleet)
        cand = str(tmp_path / "ckpt_v2")
        before = fleet.predict([4])[0, 0]
        rec = fleet.rollout(cand)
        assert rec["verdict"] == "promoted"
        assert rec["restarted"] == 3 and rec["rolled_back"] == 0
        assert all(r.ckpt_dir == os.path.abspath(cand)
                   for r in fleet.replicas)
        assert all(r.recipe.ckpt_dir == os.path.abspath(cand)
                   for r in fleet.replicas)
        after = fleet.predict([4])[0, 0]
        assert after != before  # the NEW model answers now
        assert len(world.alive()) == 3  # one process per replica, no leak
    finally:
        fleet.close()
    rollouts = _records(reg, tmp_path, "rollout")
    assert len(rollouts) == 1 and rollouts[0]["verdict"] == "promoted"


# ---- rollout races (the satellite) -----------------------------------------


def test_double_rollout_refused(world, tmp_path, monkeypatch):
    """A second concurrent rollout() is refused as its own typed record;
    the first completes untouched."""
    _pass_preflight(monkeypatch)
    fleet, reg = _mk_fleet(world, tmp_path, n=2)
    gate = threading.Event()
    entered = threading.Event()

    def slow_canary(ckpt):
        entered.set()
        gate.wait(10.0)
        return {"disagreement": 0.0, "tolerance": 0.05, "seeds": 8,
                "batches": 2, "mirrored": False, "passed": True}

    fleet._canary = slow_canary
    out = {}
    t = threading.Thread(
        target=lambda: out.update(first=fleet.rollout(
            str(tmp_path / "ckpt_v2")
        ))
    )
    t.start()
    try:
        assert entered.wait(10.0)
        second = fleet.rollout(str(tmp_path / "ckpt_v3"))
        assert second["verdict"] == "refused"
        assert "in progress" in second["error"]
        gate.set()
        t.join(timeout=20.0)
        assert out["first"]["verdict"] == "promoted"
        assert len(world.alive()) == 2
    finally:
        gate.set()
        t.join(timeout=5.0)
        fleet.close()
    rollouts = _records(reg, tmp_path, "rollout")
    assert sorted(e["verdict"] for e in rollouts) == [
        "promoted", "refused",
    ]  # exactly one record per rollout() call
    assert world.alive() == []


def test_close_during_inflight_rollout(world, tmp_path, monkeypatch):
    """close() mid-rollout: the rollout aborts, every process is reaped,
    and owed requests complete (served before close, shed after) — none
    leak, none hang."""
    _pass_preflight(monkeypatch)
    fleet, reg = _mk_fleet(world, tmp_path, n=2)
    gate = threading.Event()
    entered = threading.Event()

    def slow_canary(ckpt):
        entered.set()
        gate.wait(10.0)
        return {"disagreement": 0.0, "tolerance": 0.05, "seeds": 8,
                "batches": 2, "mirrored": False, "passed": True}

    fleet._canary = slow_canary
    out = {}
    t = threading.Thread(
        target=lambda: out.update(rec=fleet.rollout(
            str(tmp_path / "ckpt_v2")
        ))
    )
    t.start()
    assert entered.wait(10.0)
    served = fleet.submit([1])  # owed BEFORE close: must be answered
    assert served.result(timeout=10.0) is not None
    fleet.close()
    gate.set()
    t.join(timeout=20.0)
    rec = out["rec"]
    assert rec["verdict"] == "aborted"
    assert "closed" in rec["error"]
    assert rec["restarted"] == 0
    assert world.alive() == []  # nothing respawned after close
    late = fleet.submit([2])
    with pytest.raises(RequestShedError):
        late.result(timeout=2.0)


def test_replica_killed_mid_rollout_aborts_and_rolls_back(
        world, tmp_path, monkeypatch):
    """A replica killed between one drain/restart and the next aborts
    the rollout and rolls already-updated replicas back to the OLD
    checkpoint — no process leaked, the candidate never half-promoted."""
    _pass_preflight(monkeypatch)
    fleet, reg = _mk_fleet(world, tmp_path, n=3)
    try:
        _pass_canary(fleet)
        old_ckpt = fleet.replicas[0].ckpt_dir
        orig_roll = fleet._roll_one
        rolled = []

        def chaos_roll(r, ckpt):
            ok = orig_roll(r, ckpt)
            rolled.append((r.rid, ckpt))
            if len(rolled) == 1 and ckpt != old_ckpt:
                # between r0's restart and r1's drain: r2 dies for real
                world.proc_at(fleet.replicas[2].base_url).kill()
                fleet.hub.poll_once()
                fleet.hub.poll_once()  # miss_k=2 -> target_loss latched
            return ok

        fleet._roll_one = chaos_roll
        rec = fleet.rollout(str(tmp_path / "ckpt_v2"))
        assert rec["verdict"] == "aborted"
        assert "died mid-rollout" in rec["error"]
        assert rec["rolled_back"] == 1  # r0 returned to the old ckpt
        assert rec["restarted"] == 0  # nothing left on the candidate
        assert fleet.replicas[0].ckpt_dir == old_ckpt
        assert fleet.replicas[0].recipe.ckpt_dir == old_ckpt
        # r0+r1 alive on the old model, r2 dead (supervision is the
        # healer, and polling is off in this rig), nothing leaked
        assert len(world.alive()) == 2
        v = fleet.predict([3])
        assert v[0, 0] == pytest.approx(
            _ckpt_tag(old_ckpt) + 3.0
        )
    finally:
        fleet.close()
    assert world.alive() == []


def test_respawn_failure_mid_rollout_aborts(world, tmp_path, monkeypatch):
    """The replica being rolled dies at respawn (kill between drain and
    restart, spawn side): rollout aborts; supervision later heals the
    victim on the OLD checkpoint."""
    _pass_preflight(monkeypatch)
    fleet, reg = _mk_fleet(world, tmp_path, n=2, polling=True)
    try:
        _pass_canary(fleet)
        old_ckpt = fleet.replicas[0].ckpt_dir
        world.fail_next_spawn = True
        rec = fleet.rollout(str(tmp_path / "ckpt_v2"))
        assert rec["verdict"] == "aborted"
        assert rec["restarted"] == 0 and rec["rolled_back"] == 0
        # the victim's process died at drain; the supervisor respawns it
        # from the recorded recipe on the OLD checkpoint
        victim = fleet.replicas[0]
        deadline = time.monotonic() + 10.0
        while victim.restarts == 0 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert victim.restarts == 1
        assert victim.recipe.ckpt_dir == old_ckpt
        assert len(world.alive()) == 2
    finally:
        fleet.close()
    assert world.alive() == []


# ---- plumbing --------------------------------------------------------------


def test_launch_recipe_argv_env(tmp_path):
    r = crosshost.LaunchRecipe(
        cfg_path="/c/a.cfg", ckpt_dir="/k", replica="r1", seed=5,
        port_file="/p/r1.port", extra_env={"NTS_SERVE_BUCKETS": "1-4"},
    )
    argv = r.argv()
    assert "-m" in argv and "neutronstarlite_tpu.serve.crosshost" in argv
    assert argv[argv.index("--replica") + 1] == "r1"
    assert argv[argv.index("--seed") + 1] == "5"
    env = r.env()
    assert env["NTS_METRICS_PORT"] == "0"  # ephemeral, via port file
    assert env["NTS_SERVE_BUCKETS"] == "1-4"


def test_spawn_refuses_children_without_chips(world, tmp_path, monkeypatch):
    """On a TPU host every child opens all local chips and a chip belongs
    to one process: a spawn that would leave children waiting for a chip
    refuses BEFORE forking anything; a CPU-pinned fleet is untouched."""
    tpu_env = {"JAX_PLATFORMS": "tpu,cpu"}
    cpu_env = {"JAX_PLATFORMS": "cpu"}
    for free, replicas in ((1, 2), (4, 2), (0, 1)):
        monkeypatch.setattr(
            crosshost, "tpu_chips_free_for_children", lambda n=free: n
        )
        with pytest.raises(RuntimeError, match="CPU-only today"):
            crosshost.check_children_can_start(replicas, tpu_env)
        crosshost.check_children_can_start(replicas, cpu_env)
    # one child, chips free: it can open them
    monkeypatch.setattr(crosshost, "tpu_chips_free_for_children", lambda: 1)
    crosshost.check_children_can_start(1, tpu_env)
    # no TPU on the host: children fall to the CPU, nothing to refuse
    monkeypatch.setattr(
        crosshost, "tpu_chips_free_for_children", lambda: None
    )
    crosshost.check_children_can_start(3, tpu_env)

    # through spawn(): refused with zero children forked
    monkeypatch.setattr(crosshost, "tpu_chips_free_for_children", lambda: 1)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    with pytest.raises(RuntimeError, match="refusing to spawn 3"):
        crosshost.CrossHostFleet.spawn(
            str(tmp_path / "a.cfg"), str(tmp_path / "ck"), 3,
            spawn_dir=str(tmp_path / "sp"),
        )
    assert world.spawns == 0


def test_chip_probe_opens_nothing_on_the_cpu_rig():
    """JAX is live on the CPU here (conftest), so the answer comes from
    the host's chip device nodes alone: None on a host without a TPU, the
    node count on one with (the pytest parent holds no chip)."""
    from neutronstarlite_tpu.utils.platform import tpu_chip_nodes

    assert crosshost.tpu_chips_free_for_children() == (tpu_chip_nodes() or None)


def test_normalize_base_and_targets_env(monkeypatch):
    assert crosshost.normalize_base("h:1") == "http://h:1"
    assert crosshost.normalize_base("http://h:1/") == "http://h:1"
    monkeypatch.setenv("NTS_FLEET_TARGETS", "a:1, b:2 ,")
    assert crosshost.fleet_targets() == ["a:1", "b:2"]
    monkeypatch.setenv("NTS_CANARY_TOL", "0.125")
    assert crosshost.canary_tol() == 0.125
    monkeypatch.setenv("NTS_CANARY_TOL", "junk")
    assert crosshost.canary_tol() == crosshost.DEFAULT_CANARY_TOL


def test_wait_port_file_rejects_dead_child(tmp_path):
    proc = FakeProc(crosshost.LaunchRecipe(
        cfg_path="c", ckpt_dir="k", replica="r0", seed=0,
        port_file=str(tmp_path / "p.json"),
    ))
    proc.kill()
    with pytest.raises(RuntimeError, match="exited"):
        crosshost._wait_port_file(
            str(tmp_path / "p.json"), proc, time.monotonic() + 5.0,
        )


# ---- distributed request tracing (router-side spans) -----------------------


def test_router_traces_request_with_root_and_route_decision(
        world, tmp_path):
    fleet, reg = _mk_fleet(world, tmp_path, n=2)
    try:
        fleet.predict([1, 2])
    finally:
        fleet.close()
    spans = _records(reg, tmp_path, "span")
    root = next(s for s in spans if s["name"] == "fleet_request")
    assert root["status"] == "ok" and root["n_seeds"] == 2
    assert root["parent_id"] is None
    # per-request trace id: run_id:req_id — the fleet-merge join key
    assert root["trace_id"] == f"{reg.run_id}:{root['req_id']}"
    route = next(s for s in spans if s["name"] == "route_decision")
    assert route["trace_id"] == root["trace_id"]
    assert route["parent_id"] == root["span_id"]
    assert route["target"] == root["target"]


def test_router_traces_suspect_and_reroute_on_death(world, tmp_path):
    """The owed request's trace shows WHY it was slow: a suspect span
    (tagged with the error class) + a re_route span, zero sheds, and a
    root that still says ok."""
    fleet, reg = _mk_fleet(world, tmp_path, n=2)
    try:
        for _ in range(3):
            fleet.predict([1])
        world.proc_at(fleet.replicas[0].base_url).kill()
        world.proc_at(fleet.replicas[1].base_url).kill()
        # both dead: first attempts refuse; revive r1 via respawn so the
        # request eventually lands (run the supervision path by hand)
        fleet._restart_replica(fleet.replicas[1], "test")
        assert fleet.predict([5]) is not None
    finally:
        fleet.close()
    spans = _records(reg, tmp_path, "span")
    by_trace = {}
    for s in spans:
        by_trace.setdefault(s["trace_id"], []).append(s)
    # the killed-replica request: its trace holds suspect + re_route
    traced = [v for v in by_trace.values()
              if any(s["name"] == "suspect" for s in v)]
    assert traced, "no trace carries a suspect span"
    tr = traced[-1]
    suspects = [s for s in tr if s["name"] == "suspect"]
    assert all(s["error"] in ("refused", "timeout") for s in suspects)
    assert all(s["cooldown_s"] > 0 for s in suspects)
    assert any(s["name"] == "re_route" for s in tr)
    root = next(s for s in tr if s["name"] == "fleet_request")
    assert root["status"] == "ok"
    assert not [s for s in tr if s["name"] == "shed"]


def test_shed_verdict_is_traced(world, tmp_path):
    fleet, reg = _mk_fleet(world, tmp_path, n=2)
    try:
        for r in fleet.replicas:
            world.breaching.add(int(r.base_url.rsplit(":", 1)[1]))
        fleet.hub.poll_once()
        req = fleet.submit([1])
        with pytest.raises(RequestShedError):
            req.result(timeout=5.0)
    finally:
        fleet.close()
    spans = _records(reg, tmp_path, "span")
    shed = next(s for s in spans if s["name"] == "shed")
    root = next(s for s in spans if s["name"] == "fleet_request"
                and s["trace_id"] == shed["trace_id"])
    assert root["status"] == "shed"
    assert "fleet_breach" in root["reason"]
    assert shed["parent_id"] == root["span_id"]


def test_trace_off_router_emits_zero_spans(world, tmp_path, monkeypatch):
    monkeypatch.setenv("NTS_TRACE", "0")
    fleet, reg = _mk_fleet(world, tmp_path, n=2)
    try:
        fleet.predict([1])
    finally:
        fleet.close()
    assert _records(reg, tmp_path, "span") == []


# ---- trace env survives respawn (the restart-then-trace pin) ---------------


def test_spawn_pins_trace_env_and_restart_preserves_it(
        world, tmp_path, monkeypatch):
    """NTS_TRACE / NTS_METRICS_DIR / NTS_TRACE_STEP are captured into
    every launch recipe at spawn time and survive a supervised restart —
    a respawned replica keeps writing spans where the fleet merge looks.
    Caller-supplied extra_env wins over the snapshot."""
    monkeypatch.setenv("NTS_TRACE", "1")
    monkeypatch.setenv("NTS_METRICS_DIR", str(tmp_path / "obs"))
    monkeypatch.setenv("NTS_TRACE_STEP", "3")
    fleet, reg = _mk_fleet(world, tmp_path, n=2,
                           extra_env={"NTS_TRACE_STEP": "7"})
    try:
        r0 = fleet.replicas[0]
        for r in fleet.replicas:
            ee = r.recipe.extra_env
            assert ee["NTS_TRACE"] == "1"
            assert ee["NTS_METRICS_DIR"] == str(tmp_path / "obs")
            assert ee["NTS_TRACE_STEP"] == "7"  # explicit beats ambient
        # the ambient env can CHANGE (or vanish) after spawn; the
        # recipe's snapshot is what the respawn must replay
        monkeypatch.delenv("NTS_METRICS_DIR")
        world.proc_at(r0.base_url).kill()
        assert fleet._restart_replica(r0, "test")
        env = r0.recipe.env()
        assert env["NTS_TRACE"] == "1"
        assert env["NTS_METRICS_DIR"] == str(tmp_path / "obs")
        assert env["NTS_TRACE_STEP"] == "7"
        assert r0.restarts == 1
    finally:
        fleet.close()
