"""Multi-host elastic fleet tests (ISSUE 14): RPC registration with
protocol/shape validation, journal streaming over the reconcile RPC,
the router-side request ledger (torn-tail replay pinned), the
autoscaling supervisor, and ``host_loss`` chaos — the worker's machine
vanishes, journal and all, and every accepted request still finishes
exactly once.

Fast tier: protocol units over stub routers, the journal_drain frame
contract, the router-ledger torn-tail pin (in-process replicas), the
autoscale decision logic, host_loss mechanics, load-step arrivals.
Slow tier (``-m "multiproc and slow"``): the 4-worker fully-isolated
host-loss chaos soak and the autoscaler load-step soak — the ISSUE 14
acceptance criteria, end to end over real worker processes."""

import json
import pathlib
import sys
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from replicatinggpt_tpu.config import get_config
from replicatinggpt_tpu.faults import Fault, FaultPlan, installed
from replicatinggpt_tpu.faults.fleet import FLEET_STEP, KIND_HOST_LOSS
from replicatinggpt_tpu.faults.procsup import (AutoscaleConfig,
                                               ProcSupervisor, RETIRED,
                                               RUNNING, SPAWNING,
                                               SupervisorConfig,
                                               WorkerSpec,
                                               make_worker_specs,
                                               spawn_fleet,
                                               worker_spec_factory)
from replicatinggpt_tpu.serve import (EngineConfig, RequestJournal,
                                      RouterConfig)
from replicatinggpt_tpu.serve.journal import JournalBusyError
from replicatinggpt_tpu.serve.loadgen import (SessionLoadConfig,
                                              make_sessions,
                                              run_fleet_replay)
from replicatinggpt_tpu.serve.requests import Request, SamplingParams
from replicatinggpt_tpu.serve.rpc import (PROTO_VERSION, RpcClient,
                                          RpcListener, RpcProtocolError,
                                          engine_shape_hash)
from replicatinggpt_tpu.serve.worker import WorkerServer

pytestmark = [pytest.mark.fleet, pytest.mark.multiproc]

REPO = pathlib.Path(__file__).resolve().parents[1]
CFG = get_config("test-tiny").model


def _offline(prompt, n):
    import jax

    from replicatinggpt_tpu.sample import GenerateConfig, generate
    from replicatinggpt_tpu.train.state import create_train_state
    tcfg = get_config("test-tiny")
    state = create_train_state(jax.random.PRNGKey(tcfg.train.seed),
                               tcfg.model, tcfg.train)
    return np.asarray(generate(
        state.params, np.asarray(prompt, np.int32)[None, :], tcfg.model,
        GenerateConfig(max_new_tokens=n, greedy=True)))[0].tolist()


def _reqs(n, seed=7, max_new=8, prompt_len=4):
    rng = np.random.default_rng(seed)
    return [Request(
        id=f"e{seed}_{i}",
        prompt=rng.integers(1, CFG.vocab_size - 1,
                            (prompt_len,)).astype(np.int32),
        max_new_tokens=max_new, sampling=SamplingParams(greedy=True),
        rng_seed=seed * 1000 + i) for i in range(n)]


# ---------------------------------------------------------------------------
# registration handshake units (stub router, no subprocess)
# ---------------------------------------------------------------------------

class _RegStubRouter:
    """Records attach/add calls; enough surface for _handle_register."""

    def __init__(self, n):
        self.replicas = [SimpleNamespace(restarts=0) for _ in range(n)]
        self.rcfg = SimpleNamespace(step_timeout_s=5.0)
        self.supervisor = None
        self.attached = []
        self.added = []
        from replicatinggpt_tpu.utils.telemetry import NULL
        self.tel = NULL

    def attach_replica(self, idx, port, pid=None, gen=None, host=None,
                       tier=None, page_size=None):
        self.attached.append((idx, port, pid, gen, host))
        return {"kept": 0, "requeued": 0, "ghosts": 0}

    def add_replica(self, rep):
        self.added.append(rep.idx)
        self.replicas.append(rep)
        return rep.idx

    def _event(self, msg):
        pass


def _reg_doc(**over):
    doc = {"proto": PROTO_VERSION, "shape_hash": "abc",
           "worker_idx": 0, "gen": 0, "port": 1234, "pid": 42,
           "replayed": 0}
    doc.update(over)
    return doc


def test_registration_attaches_and_pins_shape(tmp_path):
    """A valid register frame attaches the router (pid/gen/peer-host
    flow over the wire); the FIRST registration pins the fleet's
    engine-shape hash, and every later worker must match it."""
    sup = ProcSupervisor([WorkerSpec(
        idx=0, cmd=[], journal_path=str(tmp_path / "j.jsonl"))])
    router = _RegStubRouter(1)
    sup.attach_router(router)
    try:
        sup.handles[0].gen = 0
        resp = sup._handle_register(_reg_doc(), "10.1.2.3")
        assert resp["idx"] == 0
        assert router.attached == [(0, 1234, 42, 0, "10.1.2.3")]
        assert sup.handles[0].state == RUNNING
        assert sup.expect_shape_hash == "abc"      # pinned
        # a second worker with a DIFFERENT shape is rejected typed
        with pytest.raises(RpcProtocolError, match="shape"):
            sup._handle_register(_reg_doc(shape_hash="zzz"), "h")
        # wrong protocol version: typed rejection too
        with pytest.raises(RpcProtocolError, match="protocol"):
            sup._handle_register(_reg_doc(proto=PROTO_VERSION + 1),
                                 "h")
        # a stale generation (pre-restart straggler) never attaches
        sup.handles[0].gen = 1
        with pytest.raises(ValueError, match="stale generation"):
            sup._handle_register(_reg_doc(gen=0), "h")
    finally:
        sup.stop_all()


def test_registration_expected_shape_from_config(tmp_path):
    """SupervisorConfig.expect_shape_hash pre-pins the fleet shape:
    the first worker is held to it too (no first-wins window)."""
    sup = ProcSupervisor(
        [WorkerSpec(idx=0, cmd=[], journal_path=str(tmp_path / "j"))],
        SupervisorConfig(expect_shape_hash="pinned"))
    sup.attach_router(_RegStubRouter(1))
    try:
        sup.handles[0].gen = 0
        with pytest.raises(RpcProtocolError, match="shape"):
            sup._handle_register(_reg_doc(shape_hash="abc"), "h")
        sup._handle_register(_reg_doc(shape_hash="pinned"), "h")
        assert sup.handles[0].state == RUNNING
    finally:
        sup.stop_all()


def test_unmanaged_worker_joins_fleet(tmp_path):
    """worker_idx=-1: a worker the supervisor never spawned (another
    machine, another operator) registers and the fleet GROWS — a new
    replica slot, attach, recorded as external."""
    sup = ProcSupervisor([WorkerSpec(
        idx=0, cmd=[], journal_path=str(tmp_path / "j.jsonl"))])
    router = _RegStubRouter(1)
    sup.attach_router(router)
    try:
        resp = sup._handle_register(
            _reg_doc(worker_idx=-1, port=5555, pid=99), "10.9.9.9")
        assert resp["idx"] == 1
        assert router.added == [1]
        assert router.attached[-1] == (1, 5555, 99, 0, "10.9.9.9")
        assert sup.external == [1]
        # its shape pinned the fleet; a mismatched second joiner fails
        with pytest.raises(RpcProtocolError):
            sup._handle_register(
                _reg_doc(worker_idx=-1, shape_hash="other"), "h")
    finally:
        sup.stop_all()


def test_rpc_protocol_error_typed_over_wire():
    """The typed rejection crosses the wire: a listener handler
    raising RpcProtocolError answers kind="protocol", and the far
    client re-raises RpcProtocolError (terminal — no retry), not a
    generic RpcError."""
    lst = RpcListener()

    def handler(doc, peer):
        raise RpcProtocolError(f"worker speaks protocol "
                               f"v{doc.get('proto')}")

    result = {}

    def client():
        c = RpcClient("127.0.0.1", lst.port, timeout_s=5.0)
        try:
            c.call("register", proto=99, idem="reg.proto-test.0")
        except Exception as e:  # noqa: BLE001 — the assertion target
            result["exc"] = e
        finally:
            c.close()

    t = threading.Thread(target=client)
    t.start()
    deadline = time.monotonic() + 10
    while "exc" not in result and time.monotonic() < deadline:
        lst.poll(handler)
        time.sleep(0.01)
    t.join(10)
    lst.close()
    assert isinstance(result.get("exc"), RpcProtocolError)
    assert "protocol v99" in str(result["exc"])


def test_worker_reregisters_after_listener_restart():
    """ROADMAP 3a remainder, pinned: registration is no longer
    once-at-startup. A worker whose router has gone SILENT (no inbound
    RPC for the idle threshold) re-sends its register frame with
    bounded backoff — and keeps retrying through the window where the
    listener is DOWN entirely, so a restarted router's fresh listener
    on the same port re-attaches it without operator action."""
    import asyncio

    from replicatinggpt_tpu.serve import worker as worker_mod

    async def scenario():
        lst = RpcListener()
        port = lst.port
        got = []

        def handler(doc, peer):
            got.append(dict(doc))
            return {"idx": 0}

        w = SimpleNamespace(stop_event=asyncio.Event(),
                            last_contact=time.monotonic() - 100.0)
        rereg = []
        task = asyncio.ensure_future(worker_mod._reregister_loop(
            w, f"127.0.0.1:{port}",
            {"port": 1, "pid": 2, "gen": 1, "worker_idx": 0,
             "replayed": 0, "proto": PROTO_VERSION, "shape_hash": "x"},
            idle_s=0.2, backoff_s=0.05, backoff_cap_s=0.4,
            on_reregister=lambda: rereg.append(time.monotonic())))
        # phase 1: silence alone triggers a re-registration
        deadline = time.monotonic() + 10
        while not got and time.monotonic() < deadline:
            lst.poll(handler)
            await asyncio.sleep(0.02)
        assert got, "no re-registration despite router silence"
        assert got[0]["op"] == "register"
        assert got[0]["gen"] == 1
        # phase 2: the listener RESTARTS (close + rebind, same port);
        # attempts in the gap fail with ConnectionError and back off
        # (bounded), then the fresh listener gets a new register frame
        lst.close()
        w.last_contact = time.monotonic() - 100.0   # router still silent
        await asyncio.sleep(0.3)                     # a few dead attempts
        lst2 = RpcListener(port=port)
        n0 = len(got)
        deadline = time.monotonic() + 10
        while len(got) <= n0 and time.monotonic() < deadline:
            lst2.poll(handler)
            await asyncio.sleep(0.02)
            w.last_contact = min(w.last_contact,
                                 time.monotonic() - 100.0)
        lst2.close()
        assert len(got) > n0, \
            "no re-registration after the listener restarted"
        assert len(rereg) >= 2
        # a healthy router (recent contact) quiets the loop again
        w.last_contact = time.monotonic()
        n1 = len(got)
        await asyncio.sleep(0.3)
        assert len(got) == n1, "re-registered despite healthy traffic"
        task.cancel()
        try:
            await task
        except asyncio.CancelledError:
            pass

    asyncio.run(scenario())


def test_engine_shape_hash_sensitivity():
    """The hash moves with anything that must agree fleet-wide (model
    arch, pool/page shape) and is stable across processes by
    construction (pure function of the configs)."""
    import dataclasses
    mcfg = get_config("test-tiny").model
    base = engine_shape_hash(mcfg, EngineConfig())
    assert base == engine_shape_hash(mcfg, EngineConfig())
    assert base != engine_shape_hash(
        dataclasses.replace(mcfg, n_layer=mcfg.n_layer + 1),
        EngineConfig())
    assert base != engine_shape_hash(mcfg, EngineConfig(pool_size=99))


# ---------------------------------------------------------------------------
# journal streaming (journal_drain frames)
# ---------------------------------------------------------------------------

class _NullEngine:
    """WorkerServer only needs the journal side here."""

    class cfg:
        vocab_size = CFG.vocab_size

    class scheduler:
        depth = 0

    n_steps = 0
    idle = True
    _active = np.zeros((1,), bool)

    class pool:
        class alloc:
            pages_in_use = prefix_hit_tokens = prompt_tokens = 0

    def in_flight_ids(self):
        return []


def test_journal_drain_bounded_frames(tmp_path):
    """journal_drain pages the condensed journal view in bounded
    frames: finish records as {id, reason}, unfinished requests as
    wire docs (eos included), cursor/eof contract honored."""
    path = str(tmp_path / "j.jsonl")
    j = RequestJournal(path)
    reqs = _reqs(5, seed=13)
    for q in reqs:
        j.record_submit(q)
    j.record_finish(reqs[0].id, "max_tokens")
    j.record_finish(reqs[1].id, "cancelled")
    j.close()
    journal = RequestJournal(path, lock=True)
    w = WorkerServer(_NullEngine(), journal=journal)
    # page with limit=2: 2 finished + 3 unfinished = 5 records
    records, cursor = [], 0
    for _ in range(10):
        resp = w.op_journal_drain({"cursor": cursor, "limit": 2})
        assert len(resp["records"]) <= 2
        records.extend(resp["records"])
        cursor = resp["cursor"]
        if resp["eof"]:
            break
    journal.close()
    finished = {r["id"]: r["reason"] for r in records
                if r["kind"] == "finished"}
    unfinished = [r["req"] for r in records if r["kind"] == "unfinished"]
    assert finished == {reqs[0].id: "max_tokens",
                        reqs[1].id: "cancelled"}
    assert [d["id"] for d in unfinished] == [q.id for q in reqs[2:]]
    # wire docs round-trip through the shared request codec
    assert unfinished[0]["prompt"] == reqs[2].prompt.tolist()
    # a journal-less worker drains empty + eof immediately
    w2 = WorkerServer(_NullEngine(), journal=None)
    resp = w2.op_journal_drain({})
    assert resp["records"] == [] and resp["eof"]


def test_journal_records_eos_token_id(tmp_path):
    """eos_token_id survives the journal round trip: a replayed
    request keeps its stop condition (token-identity across restarts
    requires it)."""
    path = str(tmp_path / "eos.jsonl")
    j = RequestJournal(path)
    q = Request(id="eos1", prompt=np.asarray([1, 2], np.int32),
                max_new_tokens=9, sampling=SamplingParams(greedy=True),
                rng_seed=3, eos_token_id=7)
    plain = _reqs(1, seed=15)[0]
    j.record_submit(q)
    j.record_submit(plain)
    j.close()
    back = {r.id: r for r in RequestJournal.unfinished(path)}
    assert back["eos1"].eos_token_id == 7
    assert back[plain.id].eos_token_id is None


# ---------------------------------------------------------------------------
# router-side request ledger (the torn-tail satellite pin)
# ---------------------------------------------------------------------------

def _params():
    import jax

    from replicatinggpt_tpu.models.gpt import init_params
    return init_params(jax.random.PRNGKey(0), CFG)


def test_router_ledger_records_submits_and_finishes(tmp_path):
    """With ledger_path set, the router journals one submit record at
    fleet acceptance and one finish record per terminal result — the
    same RequestJournal format the workers use."""
    from replicatinggpt_tpu.serve import Router
    ledger = str(tmp_path / "ledger.jsonl")
    r = Router(_params(), CFG,
               RouterConfig(n_replicas=1, ledger_path=ledger),
               EngineConfig(pool_size=2, max_queue=8))
    try:
        reqs = _reqs(2, seed=21, max_new=4)
        for q in reqs:
            assert r.submit(q) is None
        r.drain()
    finally:
        r.close()
    recs = [json.loads(ln) for ln in
            pathlib.Path(ledger).read_text().splitlines()]
    subs = [x["id"] for x in recs if x["ev"] == "submit"]
    fins = [x["id"] for x in recs if x["ev"] == "finish"]
    assert sorted(subs) == sorted(q.id for q in reqs)
    assert sorted(fins) == sorted(q.id for q in reqs)
    # recovery over a complete ledger finds nothing to requeue
    assert RequestJournal.unfinished(ledger) == []


def test_router_ledger_torn_finish_requeues_exactly_once(tmp_path):
    """THE satellite pin: a router crash mid-finish-record leaves a
    torn tail; the restarted router must requeue (not drop, not
    double-decode) the affected id. The torn-tail tolerance is the
    utils/jsonl contract: the torn line is skipped, so the id replays
    as unfinished and re-decodes deterministically — delivered once."""
    from replicatinggpt_tpu.serve import Router
    ledger = str(tmp_path / "ledger.jsonl")
    a, b = _reqs(2, seed=23, max_new=5)
    pre = RequestJournal(ledger)
    pre.record_submit(a)
    pre.record_submit(b)
    pre.record_finish(a.id, "max_tokens")
    pre.close()
    with open(ledger, "a") as f:            # the crash landed HERE
        f.write(json.dumps({"ev": "finish", "id": b.id,
                            "reason": "max_tokens"})[:17])
    r = Router(_params(), CFG,
               RouterConfig(n_replicas=2, ledger_path=ledger),
               EngineConfig(pool_size=2, max_queue=8))
    try:
        assert r.metrics.counters["fleet_ledger_recovered"] == 1
        # b is known fleet-wide while requeued: a duplicate client
        # retry is rejected, never double-decoded
        assert r.knows(b.id)
        dup = r.submit(Request(id=b.id, prompt=b.prompt,
                               max_new_tokens=5,
                               sampling=SamplingParams(greedy=True),
                               rng_seed=b.rng_seed))
        assert dup is not None and dup.finish_reason.startswith(
            "rejected")
        stream = []
        results = {}
        deadline = time.monotonic() + 60
        while not r.idle:
            assert time.monotonic() < deadline
            for res in r.step():
                results[res.id] = res
            stream.extend(r.take_new_tokens(b.id))
        # a finished long ago: NOT resurrected. b: exactly once.
        assert set(results) == {b.id}
        # the reference decodes with THIS router's weights (_params,
        # not _offline's train-state init: two different models)
        from replicatinggpt_tpu.sample import GenerateConfig, generate
        want = np.asarray(generate(
            _params(), np.asarray(b.prompt, np.int32)[None, :], CFG,
            GenerateConfig(max_new_tokens=5, greedy=True)))[0].tolist()
        assert results[b.id].tokens == want
        assert stream == want
        total_admitted = sum(
            rep.engine.metrics.counters.get("requests_admitted", 0)
            for rep in r.replicas)
        assert total_admitted == 1          # one decode, one replica
    finally:
        r.close()
    # the re-decode journaled its finish: recovery is now empty
    assert RequestJournal.unfinished(ledger) == []


def test_router_ledger_lock_excludes_second_router(tmp_path):
    from replicatinggpt_tpu.serve import Router
    ledger = str(tmp_path / "ledger.jsonl")
    r = Router(_params(), CFG,
               RouterConfig(n_replicas=1, ledger_path=ledger),
               EngineConfig(pool_size=2, max_queue=8))
    try:
        with pytest.raises(JournalBusyError):
            RequestJournal(ledger, lock=True)
    finally:
        r.close()


# ---------------------------------------------------------------------------
# elastic router surface + autoscale decision logic
# ---------------------------------------------------------------------------

def test_offered_load_and_add_replica():
    """offered_load aggregates the gauges the autoscaler reads;
    add_replica grows a remote fleet with the new slot NOT alive until
    its registration attaches."""
    from replicatinggpt_tpu.serve.router import RemoteReplica, Router
    r = Router(rcfg=RouterConfig(n_replicas=0), backends=[])
    try:
        load = r.offered_load()
        assert load == {"queued": 0, "active": 0, "n_routable": 0}
        idx = r.add_replica(RemoteReplica(0, None))
        assert idx == 0
        assert not r.replicas[0].alive          # not routable yet
        assert r.offered_load()["n_routable"] == 0
        assert r.metrics.counters["fleet_replicas_added"] == 1
        with pytest.raises(AssertionError, match="append-only"):
            r.add_replica(RemoteReplica(5, None))
    finally:
        r.close()


class _LoadStubRouter(_RegStubRouter):
    """offered_load is scripted; drain_replica recorded."""

    def __init__(self, n):
        super().__init__(n)
        self.load = {"queued": 0, "active": 0, "n_routable": n}
        self.drained = []
        self.metrics = SimpleNamespace(inc=lambda *a, **k: None)
        for rep in self.replicas:
            rep.client = None
            rep.alive = True

    def offered_load(self):
        return dict(self.load)

    def drain_replica(self, idx):
        self.drained.append(idx)
        return 0

    def mark_down(self, idx, reason=""):
        pass

    def abandon_replica(self, idx):
        pass


def test_autoscale_scales_up_on_sustained_backlog(tmp_path):
    """Backlog above up_backlog_per_worker x routable for up_patience
    ticks spawns ONE new worker (cooldown + SPAWNING gate further
    decisions); a momentary spike scales nothing."""
    router = _LoadStubRouter(1)
    sup = ProcSupervisor(
        [WorkerSpec(idx=0, cmd=[], journal_path=str(tmp_path / "j"))],
        SupervisorConfig(probe_every=0),
        autoscale=AutoscaleConfig(min_workers=1, max_workers=2,
                                  up_backlog_per_worker=2.0,
                                  up_patience=3, down_patience=4,
                                  cooldown_ticks=0),
        spec_factory=worker_spec_factory(
            str(tmp_path / "scale"), ["--preset", "test-tiny"]))
    sup.attach_router(router)
    spawned = []
    sup._spawn = lambda h: (spawned.append(h.spec.idx),
                            setattr(h, "state", SPAWNING))
    try:
        sup.handles[0].state = RUNNING
        # a one-tick spike: no action
        router.load = {"queued": 9, "active": 1, "n_routable": 1}
        sup._tick_autoscale()
        router.load = {"queued": 0, "active": 1, "n_routable": 1}
        sup._tick_autoscale()
        assert sup.scale_ups == 0 and sup._up_streak == 0
        # sustained backlog: scale-up at patience
        router.load = {"queued": 9, "active": 2, "n_routable": 1}
        for _ in range(3):
            sup._tick_autoscale()
        assert sup.scale_ups == 1 and spawned == [1]
        assert router.added == [1]             # fleet grew a slot
        assert sup.handles[-1].spec.idx == 1
        # SPAWNING gates any further decision
        for _ in range(5):
            sup._tick_autoscale()
        assert sup.scale_ups == 1
        # max_workers caps once the spawn lands
        sup.handles[-1].state = RUNNING
        for _ in range(5):
            sup._tick_autoscale()
        assert sup.scale_ups == 1
    finally:
        sup.stop_all()


def test_autoscale_scales_down_via_drain_and_retires(tmp_path):
    """A sustained lull drains the highest-index worker through the
    rolling-restart drain path; its exit is terminal (RETIRED), not a
    respawn — and min_workers floors the shrink."""
    router = _LoadStubRouter(2)
    sup = ProcSupervisor(
        [WorkerSpec(idx=i, cmd=[],
                    journal_path=str(tmp_path / f"j{i}"))
         for i in range(2)],
        SupervisorConfig(probe_every=0),
        autoscale=AutoscaleConfig(min_workers=1, max_workers=2,
                                  up_patience=2, down_patience=3,
                                  down_active_per_worker=1.0,
                                  cooldown_ticks=0),
        spec_factory=worker_spec_factory(
            str(tmp_path / "scale"), ["--preset", "test-tiny"]))
    sup.attach_router(router)
    respawned = []
    sup._spawn = lambda h: respawned.append(h.spec.idx)
    try:
        for h in sup.handles:
            h.state = RUNNING
        router.load = {"queued": 0, "active": 1, "n_routable": 2}
        for _ in range(3):
            sup._tick_autoscale()
        assert sup.scale_downs == 1
        h1 = sup.handles[1]
        assert h1.retiring and h1.intentional_stop
        assert router.drained == [1]
        # the worker exits -> RETIRED, never respawned
        sup._on_exit(h1, 0)
        assert h1.state == RETIRED and not h1.retiring
        assert respawned == []
        assert not sup.reviving            # retiring never held requeues
        # min_workers floors further shrink (1 RUNNING left)
        router.load = {"queued": 0, "active": 0, "n_routable": 1}
        for _ in range(10):
            sup._tick_autoscale()
        assert sup.scale_downs == 1
    finally:
        sup.stop_all()


# ---------------------------------------------------------------------------
# host_loss mechanics + load-step arrivals
# ---------------------------------------------------------------------------

def test_chaos_host_loss_kills_process_and_deletes_workdir(tmp_path):
    """host_loss = SIGKILL + the worker's whole private dir gone
    (journal included): the machine vanished, not just the process."""
    wd = tmp_path / "w0"
    wd.mkdir()
    jpath = wd / "journal.jsonl"
    jpath.write_text('{"ev": "submit", "id": "x"}\n')
    spec = WorkerSpec(
        idx=0, cmd=[sys.executable, "-c", "import time; time.sleep(60)"],
        journal_path=str(jpath), workdir=str(wd))
    sup = ProcSupervisor([spec], SupervisorConfig(probe_every=0))
    try:
        sup._spawn(sup.handles[0])
        h = sup.handles[0]
        assert h.proc.poll() is None
        sup.chaos_host_loss(0)
        assert h.proc.poll() is not None       # dead
        assert not wd.exists()                 # disk gone with the host
        assert any("host_loss" in e for e in sup.events)
        # the respawn is the replacement host: empty dir recreated
        sup._spawn(h)
        assert wd.exists() and not jpath.exists()
    finally:
        sup.stop_all()


def test_load_step_session_arrivals_double_then_halve():
    """SessionLoadConfig.load_step phases the SAME seeded Poisson
    draws: middle-third inter-arrival gaps exactly halve (2x rate),
    final-third gaps exactly double (rate/2)."""
    base = SessionLoadConfig(n_sessions=9, turns=1, rate=50.0, seed=4,
                             prefix_len=4, max_new_tokens=2)
    flat = make_sessions(CFG, base)
    import dataclasses
    stepped = make_sessions(
        CFG, dataclasses.replace(base, load_step=True))
    # identical sessions otherwise (same seed, same draws)
    assert [s.group for s in flat] == [s.group for s in stepped]

    def gaps(sessions):
        t = [s.due_t for s in sessions]
        return np.diff(np.concatenate([[0.0], t]))

    g0, g1 = gaps(flat), gaps(stepped)
    assert np.allclose(g1[:3], g0[:3])            # base rate
    assert np.allclose(g1[3:6], g0[3:6] / 2.0)    # doubled load
    assert np.allclose(g1[6:], g0[6:] * 2.0)      # halved load


# ---------------------------------------------------------------------------
# acceptance soaks (slow tier: -m "multiproc and slow")
# ---------------------------------------------------------------------------

def _spawn_isolated(tmp_path, n_workers, rcfg=None, scfg=None,
                    telemetry=None, **spawn_kw):
    """A fleet on FULLY ISOLATED per-worker temp dirs + a router
    ledger: no shared journal dir, registration over RPC only."""
    base = str(tmp_path / "fleet")
    specs = make_worker_specs(n_workers, base,
                              ["--preset", "test-tiny"],
                              ["--pool-size", "2", "--max-queue", "16"])
    rcfg = rcfg or RouterConfig(
        n_replicas=n_workers, journal_dir=None,
        ledger_path=str(tmp_path / "router_ledger.jsonl"),
        step_timeout_s=5.0)
    scfg = scfg or SupervisorConfig(backoff_s=0.2, probe_every=4,
                                    probe_timeout_s=1.0)
    return spawn_fleet(specs, rcfg, scfg, telemetry=telemetry,
                       **spawn_kw)


def _drain_streaming(router, sup, ids, budget_s=300.0):
    results, streams = {}, {i: [] for i in ids}
    deadline = time.monotonic() + budget_s
    while not router.idle:
        assert time.monotonic() < deadline, (
            f"fleet did not drain: done={sorted(results)} "
            f"router={router.events[-6:]} sup={sup.events[-6:]}")
        for res in router.step():
            results[res.id] = res
        for rid in streams:
            streams[rid].extend(router.take_new_tokens(rid))
        sup.tick()
    return results, streams


@pytest.mark.chaos
@pytest.mark.slow
def test_host_loss_soak_exactly_once_streams(tmp_path):
    """THE ISSUE 14 acceptance criterion: a 4-worker fleet on fully
    isolated temp dirs (no shared journal dir, registration over RPC
    only) survives host_loss chaos — worker 0's process SIGKILLed AND
    its journal/workdir deleted mid-decode — with exactly-once greedy
    streams: every stream token-identical to the no-chaos run, zero
    duplicates, zero drops. Recovery reads NOTHING from the dead
    worker's filesystem: the respawned worker replays an empty journal
    and the router requeues from its own ledger."""
    router, sup = _spawn_isolated(tmp_path, 4)
    try:
        reqs = _reqs(8, seed=31, max_new=20)
        plan = FaultPlan(Fault(site=FLEET_STEP, kind=KIND_HOST_LOSS,
                               at=4, arg=0))
        with installed(plan):
            for q in reqs:
                assert router.submit(q) is None
            results, streams = _drain_streaming(router, sup,
                                                [q.id for q in reqs])
        assert ("fleet/step", KIND_HOST_LOSS, 4) in plan.fired
        assert len(results) == len(reqs)
        for q in reqs:
            want = _offline(q.prompt, 20)
            assert results[q.id].finish_reason == "max_tokens"
            assert streams[q.id] == want, (
                f"{q.id}: stream diverged across host_loss "
                f"(drop/duplicate): {streams[q.id]} != {want}")
        h0 = sup.handles[0]
        assert h0.crash_restarts == 1
        assert h0.gen == 1
        # the replacement "host" came up with an EMPTY journal: its
        # registration reported zero replayed requests
        assert any("host_loss" in e for e in sup.events)
        attach = [e for e in sup.events
                  if "worker 0 registered+attached (gen 1" in e]
        assert attach and "kept 0" in attach[-1]
        # the router's ledger closed every id (nothing left unfinished)
        ledger = router.rcfg.ledger_path
    finally:
        sup.stop_all()
        router.close()
    assert RequestJournal.unfinished(ledger) == []


@pytest.mark.slow
def test_autoscale_load_step_soak_zero_drops(tmp_path):
    """The other acceptance half: a load-step soak (session arrivals
    double mid-run, then halve) on a 1-worker fleet with the
    autoscaler enabled shows scale-UP under the sustained backlog and
    a drain-based scale-DOWN in the lull — with zero dropped requests
    and zero recompiles after warmup."""
    base = str(tmp_path / "fleet")
    config_args = ["--preset", "test-tiny"]
    # ONE decode slot per worker: arrivals genuinely outpace a
    # single worker, so the backlog signal is real, not simulated
    engine_args = ["--pool-size", "1", "--max-queue", "64"]
    specs = make_worker_specs(1, base, config_args, engine_args)
    rcfg = RouterConfig(
        n_replicas=1, journal_dir=None,
        ledger_path=str(tmp_path / "router_ledger.jsonl"),
        step_timeout_s=5.0, retry_max=8)
    router, sup = spawn_fleet(
        specs, rcfg,
        SupervisorConfig(backoff_s=0.2, probe_every=0),
        autoscale=AutoscaleConfig(min_workers=1, max_workers=3,
                                  up_backlog_per_worker=0.5,
                                  up_patience=2,
                                  down_active_per_worker=2.0,
                                  down_patience=20, cooldown_ticks=10),
        spec_factory=worker_spec_factory(base, config_args,
                                         engine_args))
    lcfg = SessionLoadConfig(
        n_sessions=16, turns=2, n_prefix_groups=2, prefix_len=8,
        user_len_min=1, user_len_max=2, max_new_tokens=8,
        rate=2.0, think_time_s=0.5, greedy=True, seed=0,
        load_step=True)
    try:
        summary = run_fleet_replay(None, CFG, lcfg, router=router,
                                   supervisor=sup,
                                   collect_streams=True)
        # drain the lull: keep ticking until the autoscaler had its
        # chance to retire the extra workers
        deadline = time.monotonic() + 60
        while sup.scale_downs == 0 and time.monotonic() < deadline:
            router.step()
            sup.tick()
            time.sleep(0.01)
        assert sup.scale_ups >= 1, (sup.events[-10:])
        assert sup.scale_downs >= 1, (sup.events[-10:])
        assert summary["n_completed"] == summary["n_requests"], (
            "autoscaling dropped requests")
        assert summary["n_rejected"] == 0
        # every stream delivered exactly the terminal token list
        for rid, res in summary["results"].items():
            assert summary["streams"][rid] == list(res.tokens)
        assert summary["recompiles_after_warmup"] == 0
        assert any(h.state == RETIRED for h in sup.handles)
        assert sum(h.state == RUNNING for h in sup.handles) >= 1
    finally:
        sup.stop_all()
        router.close()
