"""Out-of-process fleet tests (serve/rpc.py + serve/worker.py +
faults/procsup.py): the RPC framing/codecs and ack-redelivery protocol,
the journal's cross-process exclusivity + fsync knobs, the worker
dispatch table, the supervisor's restart-budget/quarantine policy —
and, under ``-m "multiproc and slow"``, the pinned acceptance soaks:
a greedy stream token-identical across a REAL ``kill -9`` of a worker
process mid-decode, a rolling restart of every worker with zero
dropped requests and ``/readyz`` flipping 503 -> 200, cross-process
duplicate-id dedupe through a restart, and SIGSTOP (proc_hang) chaos.

The fast tier spawns at most ONE worker subprocess (the smoke); the
unit tests fake the engine/process ends of the protocol."""

import asyncio
import importlib.util
import json
import os
import pathlib
import signal
import sys
import time

import numpy as np
import pytest

from replicatinggpt_tpu.config import get_config
from replicatinggpt_tpu.faults import Fault, FaultPlan, installed
from replicatinggpt_tpu.faults.fleet import (FLEET_STEP, KIND_PROC_HANG,
                                             KIND_PROC_KILL)
from replicatinggpt_tpu.faults.netchaos import (NET_CALL, FaultyTransport,
                                                KIND_NET_CORRUPT,
                                                KIND_NET_DELAY,
                                                KIND_NET_DROP,
                                                KIND_NET_DUP,
                                                KIND_NET_PARTITION,
                                                KIND_NET_REORDER,
                                                KIND_NET_TRICKLE,
                                                net_site)
from replicatinggpt_tpu.faults.procsup import (BACKOFF, QUARANTINED,
                                               ProcSupervisor, RUNNING,
                                               SupervisorConfig,
                                               WorkerSpec,
                                               make_worker_specs,
                                               spawn_fleet)
from replicatinggpt_tpu.serve import (JournalBusyError, RequestJournal,
                                      RouterConfig)
from replicatinggpt_tpu.serve.requests import (FINISH_CANCELLED,
                                               REJECT_BAD_REQUEST,
                                               Request, RequestResult,
                                               SamplingParams)
from replicatinggpt_tpu.serve.rpc import (HEADER_BYTES,
                                          REJECT_REPLICA_DOWN, RpcClient,
                                          RpcDown, RpcError,
                                          RpcProtocolError, RpcTimeout,
                                          crc_ok, decode_header,
                                          encode_frame,
                                          request_from_wire,
                                          request_to_wire,
                                          result_from_wire,
                                          result_to_wire,
                                          serve_connection)
from replicatinggpt_tpu.serve.worker import (IDEMPOTENT_VERBS,
                                             REPLY_CACHE_SIZE,
                                             WorkerServer)

pytestmark = [pytest.mark.fleet, pytest.mark.multiproc]

REPO = pathlib.Path(__file__).resolve().parents[1]

CFG = get_config("test-tiny").model


def _offline(prompt, n):
    """Greedy reference through the same params every test-tiny worker
    builds (create_train_state is deterministic in the preset seed)."""
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
        id=f"m{seed}_{i}",
        prompt=rng.integers(1, CFG.vocab_size - 1,
                            (prompt_len,)).astype(np.int32),
        max_new_tokens=max_new, sampling=SamplingParams(greedy=True),
        rng_seed=seed * 1000 + i) for i in range(n)]


def _spawn(tmp_path, n_workers, rcfg=None, scfg=None, telemetry=None):
    jdir = str(tmp_path / "journals")
    specs = make_worker_specs(n_workers, jdir, ["--preset", "test-tiny"],
                              ["--pool-size", "2", "--max-queue", "16"])
    rcfg = rcfg or RouterConfig(n_replicas=n_workers, journal_dir=jdir,
                                step_timeout_s=5.0)
    scfg = scfg or SupervisorConfig(backoff_s=0.2, probe_every=4,
                                    probe_timeout_s=1.0)
    return spawn_fleet(specs, rcfg, scfg, telemetry=telemetry)


def _drain_streaming(router, sup, ids, budget_s=240.0):
    """Step the fleet (ticking the supervisor) while consuming the
    delivery ledger every step; returns (results, streams)."""
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


def _trace_check():
    spec = importlib.util.spec_from_file_location(
        "trace_check", REPO / "tools" / "trace_check.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# RPC protocol units (no subprocess)
# ---------------------------------------------------------------------------

def test_rpc_framing_and_bounds():
    frame = encode_frame({"op": "health", "x": 1})
    n, crc = decode_header(frame[:HEADER_BYTES])
    body = frame[HEADER_BYTES:]
    assert n == len(body)
    assert crc_ok(body, crc)
    assert json.loads(body) == {"op": "health", "x": 1}
    # a single flipped body byte must fail the checksum, not decode
    assert not crc_ok(bytes([body[0] ^ 0xFF]) + body[1:], crc)
    # a corrupt length prefix must not allocate gigabytes
    with pytest.raises(ValueError, match="frame too large"):
        decode_header((1 << 30).to_bytes(4, "big") + b"\x00" * 4)
    with pytest.raises(ValueError, match="frame too large"):
        encode_frame({"blob": "x" * (17 << 20)})


def test_rpc_wire_codecs_roundtrip():
    req = Request(id="w1", prompt=np.asarray([3, 1, 4], np.int32),
                  max_new_tokens=7,
                  sampling=SamplingParams(temperature=0.5, top_k=3,
                                          top_p=0.9, greedy=False),
                  deadline=105.0, rng_seed=42)
    doc = json.loads(json.dumps(request_to_wire(req, now=100.0)))
    back = request_from_wire(doc, now=200.0)
    assert back.id == "w1" and back.prompt.tolist() == [3, 1, 4]
    assert back.max_new_tokens == 7 and back.rng_seed == 42
    assert back.sampling == req.sampling
    # deadlines cross as REMAINING seconds, rebased on the far clock
    assert back.deadline == pytest.approx(205.0)
    assert request_from_wire(
        json.loads(json.dumps(request_to_wire(
            Request(id="w2", prompt=np.asarray([1], np.int32),
                    max_new_tokens=1,
                    sampling=SamplingParams(greedy=True)), 5.0))),
        9.0).deadline is None
    res = RequestResult(id="w1", tokens=[1, 2, 3],
                        finish_reason="max_tokens", queue_wait_s=0.1,
                        ttft_s=0.2, decode_tokens_per_s=30.0,
                        total_s=0.5)
    back = result_from_wire(json.loads(json.dumps(result_to_wire(res))))
    assert (back.id, back.tokens, back.finish_reason) == \
        ("w1", [1, 2, 3], "max_tokens")
    assert back.ttft_s == pytest.approx(0.2)


def test_rpc_client_server_roundtrip_over_socket():
    """RpcClient against a real asyncio serve_connection loop: ok
    responses, dispatch exceptions as framed RpcError (NOT a dropped
    socket), reconnect after server close raises RpcDown."""
    calls = []

    def dispatch(doc):
        calls.append(doc["op"])
        if doc["op"] == "boom":
            raise RuntimeError("engine exploded")
        return {"echo": doc.get("x")}

    async def main():
        server = await asyncio.start_server(
            lambda r, w: serve_connection(r, w, dispatch),
            "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        loop = asyncio.get_running_loop()

        def client_side():
            c = RpcClient("127.0.0.1", port, timeout_s=5.0)
            assert c.call("ping", x=3)["echo"] == 3
            with pytest.raises(RpcError, match="engine exploded"):
                c.call("boom")
            # the connection survives a dispatch error (framed, not cut)
            assert c.call("ping", x=4)["echo"] == 4
            return c

        c = await loop.run_in_executor(None, client_side)
        # client first: since Python 3.12 wait_closed() waits for every
        # open connection, so closing the server under a live client
        # deadlocks (this hung the whole tier-1 run into its time limit)
        c.close()
        server.close()
        await server.wait_closed()

        def after_close():
            # reconnect against the closed listener: RpcDown, not hang
            with pytest.raises(RpcDown):
                c.call("ping", x=5)

        await loop.run_in_executor(None, after_close)

    asyncio.run(main())
    assert calls[:3] == ["ping", "boom", "ping"]


def test_recv_exact_eof_classification():
    """EOF position decides the failure class: a peer that closes
    BETWEEN frames (read the request, never answered) is a dead/
    restarting worker — RpcDown, retry elsewhere. A peer that closes
    MID-frame (partial header or partial body) tore a frame — that is
    a protocol failure (RpcProtocolError), and the retry-once path
    must reconnect with the SAME idem key rather than re-route."""
    mode = {"m": "idle_eof"}

    async def handler(reader, writer):
        try:
            header = await reader.readexactly(HEADER_BYTES)
            n, _ = decode_header(header)
            await reader.readexactly(n)
        except asyncio.IncompleteReadError:
            return
        m = mode["m"]
        if m == "torn_header":
            writer.write(b"\x00\x00\x00")           # 3 of 8 header bytes
            await writer.drain()
        elif m == "torn_body":
            frame = encode_frame({"ok": True})
            writer.write(frame[:HEADER_BYTES + 2])  # full header, 2 of n
            await writer.drain()
        writer.close()                              # idle_eof: reply-less

    async def main():
        server = await asyncio.start_server(handler, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        loop = asyncio.get_running_loop()

        def client_side():
            c = RpcClient("127.0.0.1", port, timeout_s=5.0)
            with pytest.raises(RpcDown, match="connection closed"):
                c.call("ping")
            c.close()
            mode["m"] = "torn_header"
            with pytest.raises(RpcProtocolError, match="mid-frame"):
                c.call("ping")
            c.close()
            mode["m"] = "torn_body"
            with pytest.raises(RpcProtocolError, match="mid-frame"):
                c.call("ping")
            c.close()

        await loop.run_in_executor(None, client_side)
        server.close()
        await server.wait_closed()

    asyncio.run(main())


# ---------------------------------------------------------------------------
# journal durability satellites
# ---------------------------------------------------------------------------

def test_journal_lock_excludes_second_writer(tmp_path):
    """Exclusive flock at open: two processes (or two opens — flock is
    per open-file-description) can never append to one journal; the
    lock dies with its holder, so close() frees it."""
    path = str(tmp_path / "j.jsonl")
    j1 = RequestJournal(path, lock=True)
    with pytest.raises(JournalBusyError):
        RequestJournal(path, lock=True)
    # readers never lock: unfinished() works against a held journal
    j1.record_submit(_reqs(1)[0])
    assert len(RequestJournal.unfinished(path)) == 1
    j1.close()
    j2 = RequestJournal(path, lock=True)   # freed with the holder
    j2.close()


def test_journal_fsync_finish_knob(tmp_path, monkeypatch):
    """fsync_finish fsyncs finish records only: a lost finish would
    re-deliver a request the client saw complete, a lost submit only
    loses an un-started request the router retries."""
    synced = []
    real_fsync = os.fsync

    def counting_fsync(fd):
        synced.append(fd)
        return real_fsync(fd)

    monkeypatch.setattr(os, "fsync", counting_fsync)
    j = RequestJournal(str(tmp_path / "f.jsonl"), fsync_finish=True)
    j.record_submit(_reqs(1)[0])
    assert not synced                      # submits: flush-only
    j.record_finish(_reqs(1)[0].id, "max_tokens")
    assert len(synced) == 1                # finishes: fsynced
    j.close()
    off = RequestJournal(str(tmp_path / "g.jsonl"), fsync_finish=False)
    off.record_finish("x", "max_tokens")
    assert len(synced) == 1                # knob off: no fsync
    off.close()


def test_journal_torn_tail_contract_repinned(tmp_path):
    """The reader contract under the new writer knobs is unchanged:
    a torn final line (crash mid-append) is skipped, never raises, and
    the intact prefix replays."""
    path = str(tmp_path / "torn.jsonl")
    j = RequestJournal(path, fsync_finish=True)
    a, b = _reqs(2, seed=9)
    j.record_submit(a)
    j.record_submit(b)
    j.record_finish(a.id, "max_tokens")
    j.close()
    with open(path, "a") as f:
        f.write('{"ev": "finish", "id": "m9_1", "rea')   # torn tail
    pending = RequestJournal.unfinished(path)
    assert [r.id for r in pending] == [b.id]


def test_journal_torn_tail_with_duplicated_finish_lines(tmp_path):
    """A retried/duplicated finish append (the crash window between
    record_finish and the ack that would have suppressed the retry)
    plus a torn tail in ONE file: the reader must survive both — each
    duplicated finish counts once (last reason wins), the torn line is
    skipped, and the journal_drain view the router reconciles from
    lists every finished id exactly once."""
    path = str(tmp_path / "dupfin.jsonl")
    j = RequestJournal(path)
    a, b, c = _reqs(3, seed=13)
    for q in (a, b, c):
        j.record_submit(q)
    j.record_finish(a.id, "max_tokens")
    j.record_finish(a.id, "max_tokens")      # exact duplicate line
    j.record_finish(b.id, "max_tokens")
    j.record_finish(b.id, "cancelled")       # duplicate, new reason
    j.close()
    with open(path, "a") as f:
        f.write('{"ev": "finish", "id": "' + c.id + '", "rea')
    pending = RequestJournal.unfinished(path)
    assert [r.id for r in pending] == [c.id]     # dups never resurrect
    # the RPC-visible view: one finished record per id, last reason
    w = WorkerServer(_FakeEngine(), journal=RequestJournal(path))
    resp = w.dispatch({"op": "journal_drain", "cursor": 0})
    assert resp["eof"]
    finished = [r for r in resp["records"] if r["kind"] == "finished"]
    assert sorted((r["id"], r["reason"]) for r in finished) == \
        sorted([(a.id, "max_tokens"), (b.id, "cancelled")])
    unfinished = [r for r in resp["records"]
                  if r["kind"] == "unfinished"]
    assert [r["req"]["id"] for r in unfinished] == [c.id]
    w.journal.close()


# ---------------------------------------------------------------------------
# worker dispatch units (fake engine, no subprocess)
# ---------------------------------------------------------------------------

class _FakeAlloc:
    pages_in_use = 0
    prefix_hit_tokens = 0
    prompt_tokens = 0


class _FakePool:
    alloc = _FakeAlloc()

    def cached_prefix_tokens(self, prompt):
        return 0


class _FakeMetrics:
    counters = {"requests_admitted": 1}


class _FakeEngine:
    """The minimal host API WorkerServer drives."""

    class cfg:
        vocab_size = CFG.vocab_size

    def __init__(self, capacity=8):
        self.pool = _FakePool()
        self.metrics = _FakeMetrics()
        self.n_steps = 0
        self._active = np.zeros((2,), bool)
        self._inflight = {}
        self._finish_next = []
        self.cancelled = []
        self.journal = None
        self.capacity = capacity

    @property
    def idle(self):
        return not self._inflight

    class scheduler:
        depth = 0

    def submit(self, req):
        if req.id in self._inflight:
            return RequestResult(id=req.id, tokens=[],
                                 finish_reason=REJECT_BAD_REQUEST)
        if len(self._inflight) >= self.capacity:
            return RequestResult(id=req.id, tokens=[],
                                 finish_reason="rejected_queue_full")
        self._inflight[req.id] = req
        return None

    def step(self):
        self.n_steps += 1
        out = []
        for rid in self._finish_next:
            self._inflight.pop(rid, None)
            out.append(RequestResult(id=rid, tokens=[1, 2],
                                     finish_reason="max_tokens"))
        self._finish_next = []
        return out

    def cancel(self, rid, migrated=False):
        self.cancelled.append((rid, migrated))
        return self._inflight.pop(rid, None) is not None

    def in_flight_ids(self):
        return list(self._inflight)

    def partial_tokens(self, rid):
        return [7] if rid in self._inflight else None


def test_worker_step_redelivers_finishes_until_acked():
    """A finish stays in every step response until the router acks it —
    a response lost to a timeout or a router crash must not lose it."""
    eng = _FakeEngine()
    w = WorkerServer(eng, journal=None)
    q = _reqs(1, seed=3)[0]
    assert w.dispatch({"op": "submit",
                       "req": request_to_wire(q, 0.0)})["accepted"]
    eng._finish_next = [q.id]
    r1 = w.dispatch({"op": "step", "acks": []})
    assert [d["id"] for d in r1["finished"]] == [q.id]
    r2 = w.dispatch({"op": "step", "acks": []})   # redelivered
    assert [d["id"] for d in r2["finished"]] == [q.id]
    r3 = w.dispatch({"op": "step", "acks": [q.id]})   # acked -> pruned
    assert r3["finished"] == []
    assert r3["idle"] is True


def test_worker_drain_refuses_submits_and_journals_pending(tmp_path):
    """The rolling-restart drain: submits refuse REJECT_REPLICA_DOWN
    (non-deterministic verdict — the router tries elsewhere), in-flight
    work cancels migrated, and replay-pending requests journal a finish
    so the NEXT incarnation never resurrects them."""
    path = str(tmp_path / "w.jsonl")
    a, b = _reqs(2, seed=4)
    pre = RequestJournal(path)
    pre.record_submit(a)
    pre.record_submit(b)
    pre.close()
    # capacity 1: replay admits a, leaves b replay-pending
    eng = _FakeEngine(capacity=1)
    journal = RequestJournal(path, lock=True)
    eng.journal = journal
    w = WorkerServer(eng, journal=journal)
    n = w.replay_journal(path)
    assert n == 2 and sorted(w._in_flight_ids()) == sorted([a.id, b.id])
    assert [r.id for r in w._replay_pending] == [b.id]
    resp = w.dispatch({"op": "drain"})
    assert sorted(resp["cancelled"]) == sorted([a.id, b.id])
    assert (a.id, True) in eng.cancelled       # migrated cancel
    rej = w.dispatch({"op": "submit",
                      "req": request_to_wire(_reqs(1, seed=5)[0], 0.0)})
    assert not rej["accepted"]
    assert rej["rejection"]["finish_reason"] == REJECT_REPLICA_DOWN
    journal.close()
    # the drain journaled b's (replay-pending) finish — a future replay
    # resurrects only a, whose finish the REAL engine would have
    # journaled inside cancel(migrated=True) (pinned in test_fleet)
    assert [r.id for r in RequestJournal.unfinished(path)] == [a.id]


def test_worker_cancel_of_replay_pending_journals_finish(tmp_path):
    path = str(tmp_path / "c.jsonl")
    q = _reqs(1, seed=6)[0]
    pre = RequestJournal(path)
    pre.record_submit(q)
    pre.close()
    eng = _FakeEngine(capacity=0)          # everything replay-pends
    journal = RequestJournal(path, lock=True)
    w = WorkerServer(eng, journal=journal)
    w.replay_journal(path)
    assert [r.id for r in w._replay_pending] == [q.id]
    resp = w.dispatch({"op": "cancel", "id": q.id, "migrated": True})
    assert resp["found"]
    journal.close()
    assert RequestJournal.unfinished(path) == []


# ---------------------------------------------------------------------------
# idempotent dispatch + generation fence (fake engine, no subprocess)
# ---------------------------------------------------------------------------

def test_worker_reply_cache_suppresses_duplicates():
    """The worker-side half of exactly-once under duplication: a
    mutating frame replayed with the same idem key answers from the
    reply cache (marked idem_hit, engine untouched); a FRESH key is a
    new logical attempt and re-executes; the cache is bounded FIFO."""
    eng = _FakeEngine()
    w = WorkerServer(eng, journal=None)
    assert "submit" in IDEMPOTENT_VERBS
    q = _reqs(1, seed=62)[0]
    doc = {"op": "submit", "req": request_to_wire(q, 0.0),
           "idem": "k1"}
    d1 = w.dispatch(dict(doc))
    assert d1["accepted"] and "idem_hit" not in d1
    d2 = w.dispatch(dict(doc))                   # duplicated frame
    assert d2["accepted"] and d2["idem_hit"] is True
    assert list(eng._inflight) == [q.id]         # executed exactly once
    # a fresh key re-executes: the ENGINE's in-flight dedupe answers
    d3 = w.dispatch({**doc, "idem": "k2"})
    assert not d3["accepted"]
    assert d3["rejection"]["finish_reason"] == REJECT_BAD_REQUEST
    # bounded cache: REPLY_CACHE_SIZE newer entries evict k1 — a
    # duplicate THAT stale is a bug, not a retry, and re-executes
    for i in range(REPLY_CACHE_SIZE):
        w.dispatch({**doc, "idem": f"evict.{i}"})
    assert "k1" not in w._replies
    assert len(w._replies) == REPLY_CACHE_SIZE


def test_worker_generation_fence():
    """A frame stamped with another incarnation's gen is talking to
    the wrong process: typed RpcProtocolError carrying the 'stale
    generation' marker (the router's cue to renegotiate the attach),
    never execution. Matching or absent gens pass; gen=-1 disables
    the fence (direct-embedding tests)."""
    w = WorkerServer(_FakeEngine(), journal=None)
    w.gen = 7
    with pytest.raises(RpcProtocolError, match="stale generation 6"):
        w.dispatch({"op": "step", "acks": [], "gen": 6})
    assert w.dispatch({"op": "step", "acks": [], "gen": 7})["idle"]
    assert w.dispatch({"op": "step", "acks": []})["idle"]   # unstamped
    w.gen = -1                                   # unfenced worker
    assert w.dispatch({"op": "step", "acks": [], "gen": 3})["idle"]


# ---------------------------------------------------------------------------
# netchaos transport faults (fake engine over a real socket)
# ---------------------------------------------------------------------------

class _ChaosObserver:
    """Stands in for RemoteReplica's observer hooks: collects the
    responses the chaos layer swallowed and the partition edges."""

    def __init__(self):
        self.responses = []
        self.partitions = []

    def net_chaos_response(self, resp):
        self.responses.append(resp)

    def net_chaos_partition(self, active):
        self.partitions.append(active)


def _serve_fake_worker(w):
    """Serve ``w.dispatch`` on a real socket from a daemon asyncio
    thread; returns (port, stop)."""
    import threading
    ready = {}
    started = threading.Event()

    async def main():
        stop = asyncio.Event()
        server = await asyncio.start_server(
            lambda r, wr: serve_connection(r, wr, w.dispatch),
            "127.0.0.1", 0)
        ready["port"] = server.sockets[0].getsockname()[1]
        ready["stop"] = stop
        ready["loop"] = asyncio.get_running_loop()
        started.set()
        await stop.wait()
        server.close()
        await server.wait_closed()

    t = threading.Thread(target=lambda: asyncio.run(main()),
                         daemon=True)
    t.start()
    assert started.wait(10)

    def shutdown():
        ready["loop"].call_soon_threadsafe(ready["stop"].set)
        t.join(10)

    return ready["port"], shutdown


def test_netchaos_transport_fault_ladder():
    """Every netchaos kind end to end against a real worker socket:
    dup answers from the reply cache, reorder replays the previous
    idempotent frame (discarded response still observed), delay and
    trickle are harmless, drop raises the maybe-executed RpcTimeout,
    a two-way partition raises RpcDown without touching the wire, a
    one-way partition EXECUTES but loses the response, and the first
    clean call after is the heal edge."""
    eng = _FakeEngine(capacity=16)
    w = WorkerServer(eng, journal=None)
    port, shutdown = _serve_fake_worker(w)
    obs = _ChaosObserver()
    ft = FaultyTransport(RpcClient("127.0.0.1", port, timeout_s=5.0),
                         src="router", dst="worker0", observer=obs)
    reqs = _reqs(8, seed=61)
    sub = [{"req": request_to_wire(q, 0.0), "idem": f"lad.{i}"}
           for i, q in enumerate(reqs)]
    site = net_site("router", "worker0", "submit")
    try:
        # no plan installed: the fast path never counts an ordinal
        assert ft.call("step", acks=[])["idle"]
        assert ft._counts == {}
        plan = FaultPlan(
            Fault(site=site, kind=KIND_NET_DUP, at=0),
            Fault(site=site, kind=KIND_NET_REORDER, at=1),
            Fault(site=site, kind=KIND_NET_DELAY, at=2, arg=0.01),
            Fault(site=site, kind=KIND_NET_TRICKLE, at=3, arg=5,
                  arg2=0.001),
            Fault(site=site, kind=KIND_NET_DROP, at=4),
            Fault(site=site, kind=KIND_NET_PARTITION, at=5, arg2=0),
            Fault(site=site, kind=KIND_NET_PARTITION, at=6, arg2=1),
        )
        with installed(plan):
            # idx 0 dup: caller gets the SECOND response — the cache hit
            r0 = ft.call("submit", **sub[0])
            assert r0["accepted"] and r0["idem_hit"] is True
            assert ft.dups_injected == 1
            assert list(eng._inflight) == [reqs[0].id]
            # idx 1 reorder: lad.0 replayed first (stale dup, observed
            # + discarded), then lad.1 proceeds normally
            r1 = ft.call("submit", **sub[1])
            assert r1["accepted"] and "idem_hit" not in r1
            assert ft.dups_injected == 2
            assert obs.responses[-1]["idem_hit"] is True
            # idx 2 delay / idx 3 trickle: harmless, seams restored
            assert ft.call("submit", **sub[2])["accepted"]
            assert ft.call("submit", **sub[3])["accepted"]
            assert ft.client.send_chunking is None
            # idx 4 drop: nothing on the wire, maybe-executed timeout
            with pytest.raises(RpcTimeout, match="dropped"):
                ft.call("submit", **sub[4])
            assert reqs[4].id not in eng._inflight
            # idx 5 two-way partition: frame never leaves this host
            with pytest.raises(RpcDown, match="partitioned"):
                ft.call("submit", **sub[5])
            assert reqs[5].id not in eng._inflight
            assert obs.partitions == [True]
            # idx 6 one-way partition: EXECUTED, response lost but
            # observed (dup-suppression accounting stays exact)
            with pytest.raises(RpcTimeout, match="one-way"):
                ft.call("submit", **sub[6])
            assert reqs[6].id in eng._inflight
            assert obs.responses[-1]["accepted"]
            # idx 7 clean: the heal edge
            assert ft.call("submit", **sub[7])["accepted"]
            assert obs.partitions == [True, False]
            assert not ft.partitioned
        assert ft.dups_injected == 2
    finally:
        ft.close()
        shutdown()


def test_netchaos_corrupt_frame_typed_reject_and_idem_retry():
    """net_corrupt flips one seeded body byte: the worker's checksum
    rejects the frame with a TYPED protocol error (never a mis-decoded
    request — the engine must not see it), the frame_filter seam is
    restored, and the retry with the SAME idem key executes fresh
    (the poisoned frame never reached dispatch, so there is nothing
    in the reply cache)."""
    eng = _FakeEngine()
    w = WorkerServer(eng, journal=None)
    port, shutdown = _serve_fake_worker(w)
    ft = FaultyTransport(RpcClient("127.0.0.1", port, timeout_s=5.0),
                         src="router", dst="worker0")
    q = _reqs(1, seed=63)[0]
    kw = {"req": request_to_wire(q, 0.0), "idem": "c0"}
    try:
        # the catch-all site spelling must route to this link too
        with installed(FaultPlan(Fault(site=NET_CALL,
                                       kind=KIND_NET_CORRUPT, at=0,
                                       times=1))):
            with pytest.raises(RpcProtocolError, match="checksum"):
                ft.call("submit", **kw)
            assert ft.client.frame_filter is None
            assert eng._inflight == {}           # never dispatched
            ft.close()                           # poisoned stream
            retry = ft.call("submit", **kw)      # same idem key
        assert retry["accepted"] and "idem_hit" not in retry
        assert list(eng._inflight) == [q.id]
        assert ft.dups_injected == 0             # corruption != dup
    finally:
        ft.close()
        shutdown()


# ---------------------------------------------------------------------------
# re-registration backoff (full jitter + episode idem keys)
# ---------------------------------------------------------------------------

class _RecordingRng:
    """Deterministic stand-in for the jitter rng: records each
    uniform(a, b) bound and returns 0 (no actual sleeping)."""

    def __init__(self):
        self.bounds = []

    def uniform(self, a, b):
        self.bounds.append((a, b))
        return 0.0


class _StubWorkerLoop:
    """The two attributes _reregister_loop reads off the worker."""

    def __init__(self):
        self.stop_event = asyncio.Event()
        self.last_contact = time.monotonic() - 100.0


def test_reregister_backoff_full_jitter_bounds():
    """The backoff draws uniform(0, min(cap, base * 2^attempt)) — FULL
    jitter, so a fleet-wide partition heal cannot thundering-herd the
    router. Against a dead address the bounds double then clamp at the
    cap; the low bound is always 0."""
    from replicatinggpt_tpu.serve.worker import _reregister_loop

    async def main():
        w = _StubWorkerLoop()
        rng = _RecordingRng()
        task = asyncio.ensure_future(_reregister_loop(
            w, "127.0.0.1:1",              # nothing listens on port 1
            {"worker_idx": 0, "gen": 0},
            idle_s=0.05, backoff_s=0.5, backoff_cap_s=2.0, rng=rng))
        deadline = time.monotonic() + 30.0
        while len(rng.bounds) < 5:
            assert time.monotonic() < deadline, rng.bounds
            await asyncio.sleep(0.001)
        w.stop_event.set()
        await asyncio.wait_for(task, 10.0)
        return rng.bounds

    bounds = asyncio.run(main())
    # attempt increments BEFORE the draw: first failure already doubles
    assert bounds[:4] == [(0.0, 1.0), (0.0, 2.0), (0.0, 2.0),
                          (0.0, 2.0)]


def test_reregister_episode_idem_refresh(monkeypatch):
    """One silence episode is one logical registration: retries within
    an episode reuse its idem key (a listener that executed the attach
    but lost the response answers from its reply cache), and a NEW
    episode mints a fresh key (a new logical attach must execute)."""
    import replicatinggpt_tpu.serve.worker as worker_mod
    seen = []
    fail = {"next": True}

    async def fake_attempt(addr, doc):
        seen.append(doc["idem"])
        if fail["next"]:
            fail["next"] = False
            raise ConnectionError("refused")
        return {"ok": True}

    monkeypatch.setattr(worker_mod, "_register_attempt", fake_attempt)

    async def main():
        w = _StubWorkerLoop()
        task = asyncio.ensure_future(worker_mod._reregister_loop(
            w, "127.0.0.1:1", {"worker_idx": 1, "gen": 4},
            idle_s=0.05, backoff_s=0.001, backoff_cap_s=0.002,
            rng=_RecordingRng()))
        deadline = time.monotonic() + 30.0
        while len(seen) < 2:               # episode 1: fail, then ok
            assert time.monotonic() < deadline, seen
            await asyncio.sleep(0.001)
        fail["next"] = True                # re-arm for episode 2
        w.last_contact = time.monotonic() - 100.0   # silence again
        while len(seen) < 4:               # episode 2: fail, then ok
            assert time.monotonic() < deadline, seen
            await asyncio.sleep(0.001)
        w.stop_event.set()
        await asyncio.wait_for(task, 10.0)

    asyncio.run(main())
    assert seen[:4] == ["reg.1.4.re1", "reg.1.4.re1",
                        "reg.1.4.re2", "reg.1.4.re2"]


# ---------------------------------------------------------------------------
# supervisor policy units (fake worker processes)
# ---------------------------------------------------------------------------

class _StubReplica:
    alive = True
    wedged = False
    draining = False
    restarts = 0


class _StubRouter:
    """Records the supervisor's calls; replicas are always 'alive' so
    the zombie-escalation path stays quiet."""

    def __init__(self, n):
        self.replicas = [_StubReplica() for _ in range(n)]
        self.supervisor = None
        self.abandoned = []
        self.downs = []
        from replicatinggpt_tpu.utils.telemetry import NULL
        self.tel = NULL

    def mark_down(self, idx, reason=""):
        self.downs.append(idx)

    def abandon_replica(self, idx):
        self.abandoned.append(idx)

    def _event(self, msg):
        pass


def test_supervisor_restart_budget_ends_in_quarantine(tmp_path):
    """A worker that dies on every spawn burns its crash budget through
    exponential backoff and lands QUARANTINED, with its journal
    requeued onto survivors (abandon_replica)."""
    spec = WorkerSpec(
        idx=0, cmd=[sys.executable, "-c", "import sys; sys.exit(3)"],
        journal_path=str(tmp_path / "q.jsonl"))
    sup = ProcSupervisor([spec], SupervisorConfig(
        restart_budget=2, backoff_s=0.01, backoff_mult=2.0,
        probe_every=0))
    router = _StubRouter(1)
    sup.attach_router(router)
    assert router.supervisor is sup
    sup.start_all(wait=False)
    deadline = time.monotonic() + 30
    while sup.handles[0].state != QUARANTINED:
        assert time.monotonic() < deadline, sup.events
        sup.tick()
        time.sleep(0.005)
    h = sup.handles[0]
    assert h.crash_restarts == 3           # budget 2 -> third crash quarantines
    assert router.abandoned == [0]
    assert router.downs                    # each death marked down
    assert any("quarantined" in e for e in sup.events)
    # reviving is False once nothing is coming back
    assert not sup.reviving


def test_supervisor_reviving_reflects_backoff_and_intentional_stop(
        tmp_path):
    spec = WorkerSpec(
        idx=0, cmd=[sys.executable, "-c", "import sys; sys.exit(1)"],
        journal_path=str(tmp_path / "r.jsonl"))
    sup = ProcSupervisor([spec], SupervisorConfig(
        restart_budget=5, backoff_s=30.0, probe_every=0))
    sup.attach_router(_StubRouter(1))
    sup.start_all(wait=False)
    assert sup.reviving                    # SPAWNING counts
    deadline = time.monotonic() + 30
    while sup.handles[0].state != BACKOFF:
        assert time.monotonic() < deadline
        sup.tick()
        time.sleep(0.005)
    assert sup.reviving                    # BACKOFF counts
    sup.handles[0].state = RUNNING
    assert not sup.reviving
    sup.handles[0].intentional_stop = True   # rolling-restart window
    assert sup.reviving
    sup.stop_all()


# ---------------------------------------------------------------------------
# router guards for maybe-executed submits (no subprocess)
# ---------------------------------------------------------------------------

def _tiny_router(n=2):
    import jax

    from replicatinggpt_tpu.models.gpt import init_params
    from replicatinggpt_tpu.serve import EngineConfig, Router
    params = init_params(jax.random.PRNGKey(0), CFG)
    return Router(params, CFG, RouterConfig(n_replicas=n),
                  EngineConfig(pool_size=2, max_queue=8))


def test_submit_timeout_falls_through_and_ghost_finish_swallowed():
    """A submit RPC that TIMES OUT may still execute on the hung
    worker. The router routes the id to the next candidate
    (REJECT_REPLICA_TIMEOUT is retryable), and when the maybe-executed
    copy's finish later arrives from the wrong replica it is swallowed
    by the replica-aware stale guard — the live copy's ledger entry
    and stream are untouched."""
    from replicatinggpt_tpu.serve.router import REJECT_REPLICA_TIMEOUT
    r = _tiny_router(2)
    try:
        q = _reqs(1, seed=51, max_new=4)[0]
        r.replicas[0].submit = lambda req: RequestResult(
            id=req.id, tokens=[],
            finish_reason=REJECT_REPLICA_TIMEOUT)
        # route: replica 0 "times out", replica 1 accepts
        assert r.submit(q) is None
        assert r._inflight[q.id].replica == 1
        assert r.metrics.counters["fleet_route_fallbacks"] == 1
        # the maybe-executed copy finishes on replica 0 later:
        # swallowed, the live entry on replica 1 untouched
        ghost = RequestResult(id=q.id, tokens=[9, 9],
                              finish_reason="max_tokens")
        assert r._on_finish(ghost, 0, r.clock()) is None
        assert r.metrics.counters["fleet_stale_finishes"] == 1
        assert q.id in r._inflight and q.id not in r.results
        r.drain()
        assert r.results[q.id].finish_reason == "max_tokens"
        # after the live copy delivered, a straggler duplicate from
        # the hung replica is a ghost — swallowed, result intact
        assert r._on_finish(ghost, 0, r.clock()) is None
        assert r.results[q.id].finish_reason == "max_tokens"
    finally:
        r.close()


def test_finish_from_wrong_replica_is_swallowed():
    """The ledger is replica-keyed: a finish arriving from a replica
    the id is NOT routed to (timed-out submit that executed anyway, a
    pre-migration straggler) must not pop the live copy's entry or
    surface a result."""
    r = _tiny_router(2)
    try:
        q = _reqs(1, seed=52, max_new=4)[0]
        assert r.submit(q) is None
        owner = r._inflight[q.id].replica
        stale = RequestResult(id=q.id, tokens=[1],
                              finish_reason="cancelled")
        assert r._on_finish(stale, 1 - owner, r.clock()) is None
        assert r.metrics.counters["fleet_stale_finishes"] == 1
        assert r._inflight[q.id].replica == owner
        r.drain()
        assert r.results[q.id].finish_reason == "max_tokens"
    finally:
        r.close()


def test_config_override_args_round_trips_model_config():
    """`serve --multiproc` must spawn workers serving the SAME model
    the operator asked for: every add_config_flags model override set
    on the parent's args must survive the trip through
    config_override_args -> a fresh parser -> config_from_args."""
    import argparse

    from replicatinggpt_tpu.config import (add_config_flags,
                                           config_from_args,
                                           config_override_args)

    def parse(argv):
        p = argparse.ArgumentParser()
        add_config_flags(p)
        return p.parse_args(argv)

    argv = ["--preset", "test-tiny", "--n-layer", "3", "--n-head", "4",
            "--n-embd", "64", "--block-size", "48", "--vocab-size",
            "80", "--dropout", "0.1", "--dtype", "bfloat16",
            "--attention", "einsum", "--decode-cache-layout", "packed",
            "--remat"]
    parent = parse(argv)
    forwarded = parse(["--preset", parent.preset]
                      + config_override_args(parent))
    assert config_from_args(forwarded).model == \
        config_from_args(parent).model
    # unset overrides forward nothing (workers keep preset defaults)
    assert config_override_args(parse(["--preset", "test-tiny"])) == []


# ---------------------------------------------------------------------------
# tier-1 subprocess smoke (one real worker process)
# ---------------------------------------------------------------------------

def test_worker_process_smoke_parity(tmp_path):
    """One real serve-worker subprocess behind the router: greedy
    parity vs offline generate, the cross-process journal flock (a
    second writer in THIS process gets JournalBusyError while the
    worker lives), the RPC registration handshake (no ready files
    anywhere — the workdir is the worker's PRIVATE dir), and a clean
    shutdown that frees the lock and leaves submit+finish records."""
    router, sup = _spawn(tmp_path, 1)
    try:
        h = sup.handles[0]
        # registration attached the router: pid/gen/host flowed over
        # the RPC handshake, not a filesystem artifact
        rep = router.replicas[0]
        assert rep.pid == h.pid and rep.gen == 0
        assert h.state == "running"
        assert sup.expect_shape_hash     # pinned by the registration
        # no ready files exist anywhere in the worker's private dir
        assert not [p for p in pathlib.Path(h.spec.workdir).iterdir()
                    if "ready" in p.name]
        # the worker holds the exclusive flock on its journal
        with pytest.raises(JournalBusyError):
            RequestJournal(h.spec.journal_path, lock=True)
        reqs = _reqs(3, seed=11, max_new=6)
        for q in reqs:
            assert router.submit(q) is None
        results, streams = _drain_streaming(router, sup,
                                            [q.id for q in reqs])
        assert len(results) == 3
        for q in reqs:
            want = _offline(q.prompt, 6)
            assert results[q.id].tokens == want
            assert streams[q.id] == want
        # health carries the worker's identity + engine counters
        health = router.replicas[0].health()
        assert health["pid"] == h.pid and health["warmed"]
    finally:
        sup.stop_all()
        router.close()
    # lock freed with the process; journal holds the full history
    j = RequestJournal(sup.handles[0].spec.journal_path, lock=True)
    j.close()
    recs = pathlib.Path(
        sup.handles[0].spec.journal_path).read_text()
    assert '"ev": "submit"' in recs and '"ev": "finish"' in recs


def test_step_rpc_round_trips_amortized_by_decode_window(tmp_path):
    """The worker's step RPC returns the FULL token window per call
    (and journals/redelivers finishes once per window, not per token):
    with --decode-window 16 forwarded to the worker, step-RPC round
    trips per generated token drop >= 4x vs the k=1 identity — a
    blocked worker with ONE active slot needs at least one step RPC
    per token by construction, so <= 0.25 RPCs/token IS the >= 4x
    drop. Greedy stream stays byte-identical to offline generate."""
    jdir = str(tmp_path / "journals")
    specs = make_worker_specs(
        1, jdir, ["--preset", "test-tiny"],
        ["--pool-size", "2", "--max-queue", "16",
         "--decode-window", "16"])
    router, sup = spawn_fleet(
        specs, RouterConfig(n_replicas=1, journal_dir=jdir,
                            step_timeout_s=30.0),
        SupervisorConfig(backoff_s=0.2, probe_every=10_000,
                         probe_timeout_s=5.0))
    try:
        rep = router.replicas[0]
        n_steps = {"step": 0}
        orig = rep._call

        def counted(op, **kw):
            if op == "step":
                n_steps["step"] += 1
            return orig(op, **kw)

        rep._call = counted
        req = Request(id="w0",
                      prompt=np.asarray([32, 39, 63], np.int32),
                      max_new_tokens=28,
                      sampling=SamplingParams(greedy=True))
        assert router.submit(req) is None
        results, streams = _drain_streaming(router, sup, ["w0"])
        assert results["w0"].tokens == _offline(req.prompt, 28)
        assert streams["w0"] == results["w0"].tokens
        per_token = n_steps["step"] / 28
        assert per_token <= 0.25, (
            f"{n_steps['step']} step RPCs for 28 tokens "
            f"({per_token:.3f}/token) — window not amortizing the RPC "
            f"cadence")
    finally:
        sup.stop_all()
        router.close()


# ---------------------------------------------------------------------------
# pinned acceptance soaks (slow tier: -m "multiproc and slow")
# ---------------------------------------------------------------------------

@pytest.mark.chaos
@pytest.mark.slow
def test_sigkill_mid_decode_exactly_once_streams(tmp_path):
    """THE pinned property: a REAL ``kill -9`` of a worker process
    mid-decode costs nothing — the supervisor restarts it, the worker
    replays its journal, the router reconciles via the delivery
    ledger, and every greedy stream is token-identical to an
    uninterrupted run with zero drops and zero duplicates. A SIGSTOP
    (proc_hang) lands on the other worker mid-recovery for good
    measure, both through the standard FaultPlan seam. The
    router-emitted worker-track trace must validate."""
    from replicatinggpt_tpu.utils.telemetry import Telemetry
    tel = Telemetry()
    router, sup = _spawn(tmp_path, 2, telemetry=tel)
    try:
        reqs = _reqs(4, seed=21, max_new=24)
        plan = FaultPlan(
            Fault(site=FLEET_STEP, kind=KIND_PROC_KILL, at=4, arg=0),
            Fault(site=FLEET_STEP, kind=KIND_PROC_HANG, at=8,
                  arg=3, arg2=1))
        with installed(plan):
            for q in reqs:
                assert router.submit(q) is None
            results, streams = _drain_streaming(router, sup,
                                                [q.id for q in reqs])
        assert ("fleet/step", KIND_PROC_KILL, 4) in plan.fired
        assert ("fleet/step", KIND_PROC_HANG, 8) in plan.fired
        assert len(results) == 4
        for q in reqs:
            want = _offline(q.prompt, 24)
            assert results[q.id].finish_reason == "max_tokens"
            assert streams[q.id] == want, (
                f"{q.id}: stream diverged across SIGKILL "
                f"(drop/duplicate): {streams[q.id]} != {want}")
        assert sup.handles[0].crash_restarts == 1
        assert router.metrics.counters["fleet_replica_downs"] >= 1
        assert any("CHAOS proc_kill" in e for e in sup.events)
        assert any("CHAOS proc_hang" in e for e in sup.events)
    finally:
        sup.stop_all()
        router.close()
    trace = tmp_path / "multiproc_trace.json"
    tel.export_chrome_trace(str(trace))
    tel.close()
    errors = _trace_check().check_trace(str(trace), min_requests=4)
    assert errors == []


@pytest.mark.chaos
@pytest.mark.slow
def test_rolling_restart_zero_drops_and_readyz_flip(tmp_path):
    """THE other pinned property: a rolling restart of EVERY worker
    (here: a single-worker fleet — the hardest case, with a
    zero-routable window) completes with zero dropped requests,
    token-identical streams, and ``readyz`` flipping not-ready ->
    ready; the requeue ladder holds its retry budget through the
    window instead of exhausting against a fleet mid-recovery."""
    router, sup = _spawn(tmp_path, 1)
    try:
        assert router.readyz()["ok"]
        reqs = _reqs(4, seed=31, max_new=20)
        for q in reqs:
            assert router.submit(q) is None
        results, streams = {}, {q.id: [] for q in reqs}
        for _ in range(3):                 # tokens flowing first
            for res in router.step():
                results[res.id] = res
            for rid in streams:
                streams[rid].extend(router.take_new_tokens(rid))
            sup.tick()
        sup.start_rolling_restart()
        saw_not_ready = 0
        deadline = time.monotonic() + 240
        while not router.idle or sup.rolling_active:
            assert time.monotonic() < deadline, (
                sup.events[-6:], router.events[-6:])
            for res in router.step():
                results[res.id] = res
            for rid in streams:
                streams[rid].extend(router.take_new_tokens(rid))
            sup.tick()
            if not router.readyz()["ok"]:
                saw_not_ready += 1
        assert saw_not_ready > 0, \
            "readyz never reported 503 during the zero-worker window"
        assert router.readyz()["ok"], "readyz must flip back to 200"
        h = sup.handles[0]
        assert h.gen == 1 and h.crash_restarts == 0   # free restart
        assert len(results) == 4, "rolling restart dropped requests"
        for q in reqs:
            want = _offline(q.prompt, 20)
            assert results[q.id].finish_reason == "max_tokens"
            assert streams[q.id] == want
        assert any("rolling restart complete" in e for e in sup.events)
    finally:
        sup.stop_all()
        router.close()


@pytest.mark.chaos
@pytest.mark.slow
def test_duplicate_id_during_restart_never_double_decoded(tmp_path):
    """Cross-process mirror of the PR-8 in-process pin: an id whose
    worker was SIGKILLed is STILL in flight fleet-wide while the
    restart runs — a duplicate submit (client retry) is rejected, and
    after the restart the original delivers exactly once."""
    router, sup = _spawn(tmp_path, 2)
    try:
        q = _reqs(1, seed=41, max_new=20)[0]
        assert router.submit(q) is None
        streams = {q.id: []}
        results = {}
        # let tokens flow, then kill the owning worker
        deadline = time.monotonic() + 60
        while not streams[q.id]:
            assert time.monotonic() < deadline
            for res in router.step():
                results[res.id] = res
            streams[q.id].extend(router.take_new_tokens(q.id))
            sup.tick()
        owner = router._inflight[q.id].replica
        os.kill(sup.handles[owner].pid, signal.SIGKILL)
        # the duplicate arrives while the worker is dead/restarting
        dup = router.submit(Request(
            id=q.id, prompt=q.prompt, max_new_tokens=20,
            sampling=SamplingParams(greedy=True), rng_seed=q.rng_seed))
        assert dup is not None
        assert dup.finish_reason == REJECT_BAD_REQUEST
        assert router.metrics.counters["fleet_dedup_rejects"] == 1
        more, streams2 = _drain_streaming(router, sup, [q.id])
        results.update(more)
        streams[q.id].extend(streams2[q.id])
        want = _offline(q.prompt, 20)
        assert results[q.id].tokens == want
        assert streams[q.id] == want       # exactly once, no double decode
    finally:
        sup.stop_all()
        router.close()


@pytest.mark.slow
def test_bench_fleet_multiproc_emits_tagged_artifact(tmp_path, capsys):
    """`bench.py --mode fleet --multiproc --fleet-kill-at` end to end:
    the artifact is tagged multiproc + proc_kill and carries the
    per-worker pid/gen/restart counts, requeue latency, and fleet
    TTFT the tooling satellite names — and the REAL SIGKILL mid-run
    still completes every turn."""
    sys.path.insert(0, str(REPO))
    try:
        import bench
    finally:
        sys.path.pop(0)
    bench._EMITTED = False     # emit() is first-caller-wins per process;
    #                            another bench test may have consumed it
    args = bench.main.__globals__["argparse"].Namespace(
        preset="test-tiny", serve_pool=4, serve_rate=200.0,
        serve_max_new_tokens=6, serve_page_size=4, serve_n_pages=0,
        fleet_replicas=2, fleet_sessions=5, fleet_turns=2,
        fleet_prefix_groups=2, fleet_prefix_len=8, fleet_kill_at=8,
        fleet_journal_dir=str(tmp_path), trace_out=None,
        metrics_timeline=None, metrics_out=None, multiproc=True,
        platform="cpu", fleet_load_step=False, fleet_host_loss=False, net_chaos=False)
    bench.bench_fleet(args)
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    assert lines, "bench_fleet emitted no artifact JSON"
    doc = json.loads(lines[-1])
    assert doc["metric"] == "fleet_replay_aggregate_tokens_per_sec"
    assert doc["value"] > 0
    assert doc["multiproc"] is True
    assert doc["chaos"] == "proc_kill"
    assert doc["n_completed"] == doc["n_requests"] == 10
    assert {"fleet_ttft_p50_ms", "fleet_ttft_p99_ms",
            "requeue_latency_p50_ms",
            "requeue_latency_p99_ms"} <= set(doc)
    workers = {w["worker"]: w for w in doc["workers"]}
    assert workers[0]["crash_restarts"] == 1     # the real SIGKILL
    assert workers[0]["gen"] == 1
    assert workers[1]["crash_restarts"] == 0
    assert all(isinstance(w["pid"], int) for w in doc["workers"])


@pytest.mark.chaos
@pytest.mark.slow
def test_bench_fleet_net_chaos_emits_tagged_artifact(tmp_path, capsys):
    """`bench.py --mode fleet --multiproc --net-chaos` end to end: the
    wire-fault ladder (dup/reorder/delay/drop/one-way-partition) runs
    against REAL worker processes mid-replay, every turn still
    completes, and the artifact is tagged net_chaos with the
    protocol-hardening counters in its router block."""
    sys.path.insert(0, str(REPO))
    try:
        import bench
    finally:
        sys.path.pop(0)
    bench._EMITTED = False
    args = bench.main.__globals__["argparse"].Namespace(
        preset="test-tiny", serve_pool=4, serve_rate=200.0,
        serve_max_new_tokens=6, serve_page_size=4, serve_n_pages=0,
        fleet_replicas=2, fleet_sessions=5, fleet_turns=2,
        fleet_prefix_groups=2, fleet_prefix_len=8, fleet_kill_at=-1,
        fleet_journal_dir=str(tmp_path), trace_out=None,
        metrics_timeline=None, metrics_out=None, multiproc=True,
        platform="cpu", fleet_load_step=False, fleet_host_loss=False, net_chaos=True)
    bench.bench_fleet(args)
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    assert lines, "bench_fleet emitted no artifact JSON"
    doc = json.loads(lines[-1])
    assert doc["chaos"] == "net_chaos"
    assert doc["n_completed"] == doc["n_requests"] == 10
    # the hardened protocol absorbed the ladder: every injected
    # duplicate that reached a worker answered from its reply cache
    assert doc["router"].get("rpc_dup_suppressed", 0) >= 1
    assert doc["value"] > 0


@pytest.mark.chaos
@pytest.mark.slow
def test_sse_stream_token_identical_across_sigkill(tmp_path):
    """The acceptance pin at the FRONT DOOR: a greedy SSE stream over
    real HTTP is token-identical with zero drops/duplicates across a
    real SIGKILL of the worker process mid-decode — the client sees
    one uninterrupted stream and one done event."""
    from replicatinggpt_tpu.serve.http import ServeApp
    router, sup = _spawn(tmp_path, 1)
    app = ServeApp(router, supervisor=sup, idle_timeout_s=0)

    async def main():
        host, port = await app.start()
        try:
            r, w = await asyncio.open_connection(host, port)
            payload = json.dumps({"id": "sse1", "prompt": [1, 2, 3],
                                  "max_new_tokens": 24,
                                  "greedy": True}).encode()
            w.write(b"POST /v1/submit HTTP/1.1\r\nHost: t\r\n"
                    + f"Content-Length: {len(payload)}\r\n\r\n".encode()
                    + payload)
            await w.drain()
            data = await r.read()
            assert b" 200 " in data.split(b"\r\n", 1)[0]
            w.close()
            await w.wait_closed()

            r, w = await asyncio.open_connection(host, port)
            w.write(b"GET /v1/stream/sse1 HTTP/1.1\r\nHost: t\r\n\r\n")
            await w.drain()
            # kill the worker once tokens are flowing
            buf = b""
            while buf.count(b"\ndata: ") < 3:
                chunk = await asyncio.wait_for(r.read(4096), timeout=60)
                assert chunk, f"stream closed early: {buf!r}"
                buf += chunk
            os.kill(sup.handles[0].pid, signal.SIGKILL)
            while b"event: done" not in buf:
                chunk = await asyncio.wait_for(r.read(4096),
                                               timeout=240)
                assert chunk, f"stream closed early: {buf!r}"
                buf += chunk
            w.close()
            await w.wait_closed()
            return buf
        finally:
            await app.stop()

    buf = asyncio.run(main())
    events = []
    for block in buf.partition(b"\r\n\r\n")[2].decode().split("\n\n"):
        ev, dat = "message", None
        for line in block.splitlines():
            if line.startswith("event: "):
                ev = line[len("event: "):]
            elif line.startswith("data: "):
                dat = json.loads(line[len("data: "):])
        if dat is not None:
            events.append((ev, dat))
    toks = [d["token"] for ev, d in events if ev == "message"]
    done = [d for ev, d in events if ev == "done"]
    want = _offline([1, 2, 3], 24)
    assert toks == want, (
        f"SSE stream diverged across SIGKILL: {toks} != {want}")
    assert len(done) == 1
    assert done[0]["finish_reason"] == "max_tokens"
    assert done[0]["n_tokens"] == 24
    assert sup.handles[0].crash_restarts == 1
    sup.stop_all()
    router.close()
