"""Worker process: one Engine behind the serve/rpc.py socket protocol.

``python -m replicatinggpt_tpu serve-worker`` is the unit a real
deployment schedules — on THIS machine or any other that can reach
the router: it owns one engine (its own params, KV pool, compile
caches — a whole interpreter whose death takes nothing else with it),
an exclusively-locked crash journal on its own PRIVATE disk, and an
RPC socket the router drives. Nothing here assumes a filesystem
shared with the router: the worker announces itself over the network
(``register``), and its journal's content crosses the wire
(``journal_drain``). The router process (serve/router.py,
:class:`~.router.RemoteReplica`) holds the in-flight ledger (mirrored
to the router's OWN crash journal); the supervisor
(faults/procsup.py) holds the restart + autoscale policy; this
process holds the only thing that is actually expensive — the
compiled model — and the journal that makes losing it survivable.
Losing the journal TOO (host loss) is survivable one level up, from
the router's ledger.

Startup sequence (the order is the crash-recovery contract):

1. build + **warm** the engine (one throwaway greedy token through the
   decode path, un-journaled) — readiness means "the next request pays
   no compile";
2. open the journal with ``lock=True`` (flock: a not-quite-dead
   previous incarnation still holding it fails THIS process loudly
   rather than interleaving two writers) and ``fsync_finish`` on. The
   journal is **worker-local** storage: the router never opens it —
   its content crosses the network through the ``journal_drain`` RPC;
3. **replay** the journal: every accepted-but-unfinished request from
   the previous incarnation is resubmitted into the fresh engine — it
   regenerates deterministically from token 0, and the router's
   delivery ledger suppresses the prefix the client already saw
   (exactly-once across ``kill -9``, pinned in
   tests/test_fleet_multiproc.py). Requests the admission queue cannot
   hold yet stay in a pending list retried before every step;
4. bind the RPC server (port 0 = ephemeral) and **register** with the
   fleet over the network: one ``register`` frame to ``--router-addr``
   carrying ``{port, pid, gen, replayed, worker_idx, proto,
   shape_hash}`` (serve/rpc.py). The supervisor's
   :class:`~..serve.rpc.RpcListener` answers and attaches the router —
   only now is the worker routable. No ready files, no shared
   filesystem: this is the handshake that makes the worker placeable
   on any host that can reach the router. A protocol-version or
   engine-shape mismatch is rejected HERE with a typed
   :class:`~..serve.rpc.RpcProtocolError` (exit code 3), never
   mid-traffic.

The worker never steps itself: the router's ``step`` RPC is the one
driver, so fleet scheduling stays single-threaded and deterministic
across the process boundary exactly as it is within one. Finished
results are buffered until the router acks them (serve/rpc.py's
redelivery contract); committed tokens for active slots piggyback on
every step response (the stream-drain the delivery ledger reads).
"""

from __future__ import annotations

import asyncio
import json
import os
import random
import signal
import sys
import time
from collections import OrderedDict
from typing import Dict, List, Optional

from .engine import Engine
from .journal import RequestJournal
from .requests import FINISH_CANCELLED, Request, RequestResult
from .rpc import (HEADER_BYTES, JOURNAL_DRAIN_LIMIT, PROTO_VERSION,
                  REJECT_REPLICA_DOWN, RpcProtocolError, crc_ok,
                  decode_header, encode_frame, request_from_wire,
                  request_to_wire, result_to_wire, serve_connection)


#: re-registration pacing (ROADMAP 3a remainder): a worker that
#: registered once but then hears NOTHING from the router for
#: REREGISTER_IDLE_S seconds assumes the router (or its listener)
#: restarted and lost the attachment — it re-announces itself with
#: bounded exponential backoff until a listener answers again. A
#: healthy router drives the worker every step, so silence IS the
#: signal; re-registering an already-attached worker is idempotent
#: (the supervisor's handler re-attaches at the same gen).
REREGISTER_IDLE_S = 5.0
REREGISTER_BACKOFF_S = 0.5
REREGISTER_BACKOFF_CAP_S = 10.0

#: Mutating verbs whose dispatch consults the reply cache (graftlint
#: GL024 holds this tuple against the registry in
#: analysis/contracts.py): a duplicated or blindly-retried frame
#: carrying an ``idem`` key the worker has already answered returns
#: the CACHED reply (marked ``idem_hit``) instead of re-executing —
#: the worker-side half of exactly-once under duplication. Read-only
#: verbs (step has its own ack/redeliver protocol; health, prefix,
#: summary, stream_drain are pure reads) stay uncached.
IDEMPOTENT_VERBS = ("submit", "page_transfer", "journal_drain")

#: bounded reply cache: plenty for every in-flight retry window (a
#: duplicate older than 256 mutating calls is not a retry, it is a
#: bug), small enough to never matter in memory
REPLY_CACHE_SIZE = 256


class WorkerServer:
    """Dispatch table around one engine (single-threaded: runs inside
    the asyncio loop, which is the worker's only thread of control)."""

    def __init__(self, engine: Engine,
                 journal: Optional[RequestJournal],
                 clock=time.monotonic):
        self.engine = engine
        self.journal = journal
        self.clock = clock
        self.draining = False
        self.warmed = False
        #: this incarnation's generation (faults/procsup.py assigns it
        #: at spawn; -1 = unfenced, for direct-embedding tests). The
        #: dispatch gate rejects calls stamped with any OTHER
        #: generation — a router still holding a connection to a
        #: partitioned-then-replaced incarnation gets a typed "stale
        #: generation" protocol error, never a quiet wrong-process
        #: mutation.
        self.gen = -1
        #: monotonic timestamp of the last inbound router RPC — the
        #: re-registration loop's silence detector
        self.last_contact = time.monotonic()
        #: idempotency reply cache (bounded, insertion-ordered): the
        #: last reply per idem key on mutating verbs — dispatch
        #: consults it so duplicated frames answer without re-executing
        self._replies: "OrderedDict[str, dict]" = OrderedDict()
        self.stop_event = asyncio.Event()
        #: finished results not yet acked by the router — redelivered
        #: in every step response until an ack prunes them (a response
        #: lost to a timeout/reconnect must not lose a finish)
        self._finished: Dict[str, RequestResult] = {}
        #: journal-replayed requests the admission queue could not hold
        #: yet (retried before every step)
        self._replay_pending: List[Request] = []
        #: journal_drain paging snapshot (one disk read per drain
        #: session; reset at eof / a fresh cursor-0 call)
        self._drain_snapshot: Optional[List[dict]] = None
        self.n_replayed = 0

    # ------------------------------------------------------------ replay

    def replay_journal(self, path: str) -> int:
        """Resubmit the previous incarnation's unfinished requests."""
        pending = RequestJournal.unfinished(path)
        self.n_replayed = len(pending)
        for req in pending:
            rej = self.engine.submit(req)
            if rej is not None:
                self._replay_pending.append(req)
        return self.n_replayed

    def _retry_replays(self) -> None:
        still: List[Request] = []
        for req in self._replay_pending:
            if self.engine.submit(req) is not None:
                still.append(req)
        self._replay_pending = still

    # ---------------------------------------------------------- dispatch

    def dispatch(self, doc: dict) -> dict:
        op = doc.get("op")
        fn = getattr(self, f"op_{op}", None)
        if fn is None:
            raise ValueError(f"unknown op {op!r}")
        self.last_contact = time.monotonic()
        gen = doc.get("gen")
        if gen is not None and self.gen >= 0 and int(gen) != self.gen:
            # the generation fence: a caller stamped with another
            # incarnation's gen is talking to the wrong process —
            # typed rejection, never execution (the router classifies
            # the "stale generation" marker and re-resolves)
            raise RpcProtocolError(
                f"stale generation {gen} (worker at gen {self.gen})")
        idem = doc.get("idem")
        if idem is not None and op in IDEMPOTENT_VERBS:
            cached = self._replies.get(idem)
            if cached is not None:
                # a duplicated/retried mutating frame: answer from the
                # reply cache — the original execution's exact
                # response, marked so the router's suppression counter
                # can account for it
                return {**cached, "idem_hit": True}
            resp = fn(doc) or {}
            self._replies[idem] = resp
            while len(self._replies) > REPLY_CACHE_SIZE:
                self._replies.popitem(last=False)
            return resp
        return fn(doc)

    def _in_flight_ids(self) -> List[str]:
        return (self.engine.in_flight_ids()
                + [r.id for r in self._replay_pending])

    def _gauges(self) -> dict:
        eng = self.engine
        a = eng.pool.alloc
        return {
            "queue_depth": eng.scheduler.depth,
            "slots_active": int(eng._active.sum()),
            "pages_in_use": a.pages_in_use,
            "prefix_hit_tokens": a.prefix_hit_tokens,
            "prompt_tokens": a.prompt_tokens,
            "n_steps": eng.n_steps,
            "idle": (eng.idle and not self._replay_pending
                     and not self._finished),
            "warmed": self.warmed,
        }

    def _partials(self) -> Dict[str, List[int]]:
        out: Dict[str, List[int]] = {}
        for rid in self.engine.in_flight_ids():
            toks = self.engine.partial_tokens(rid)
            if toks is not None:
                out[rid] = toks
        return out

    def op_submit(self, doc: dict) -> dict:
        if self.draining:
            return {"accepted": False,
                    "rejection": result_to_wire(RequestResult(
                        id=doc["req"]["id"], tokens=[],
                        finish_reason=REJECT_REPLICA_DOWN))}
        req = request_from_wire(doc["req"], self.clock())
        rej = self.engine.submit(req)
        if rej is None:
            return {"accepted": True}
        return {"accepted": False, "rejection": result_to_wire(rej)}

    def op_step(self, doc: dict) -> dict:
        for rid in doc.get("acks", []):
            self._finished.pop(rid, None)
        self._retry_replays()
        for res in self.engine.step():
            self._finished[res.id] = res
        return {
            "finished": [result_to_wire(r)
                         for r in self._finished.values()],
            "partials": self._partials(),
            **self._gauges(),
        }

    def op_stream_drain(self, doc: dict) -> dict:
        return {"partials": self._partials(), **self._gauges()}

    def op_cancel(self, doc: dict) -> dict:
        rid = doc["id"]
        migrated = bool(doc.get("migrated"))
        found = self.engine.cancel(rid, migrated=migrated)
        if not found:
            # a replay-pending id is in flight too (journal says so):
            # cancelling it must journal a finish or a future restart
            # would resurrect it
            for i, req in enumerate(self._replay_pending):
                if req.id == rid:
                    del self._replay_pending[i]
                    if self.journal is not None:
                        self.journal.record_finish(rid, FINISH_CANCELLED)
                    found = True
                    break
        return {"found": found}

    def op_prefix(self, doc: dict) -> dict:
        import numpy as np
        prompt = np.asarray(doc["prompt"],
                            np.int32)
        return {"tokens": int(
            self.engine.pool.cached_prefix_tokens(prompt))}

    def op_page_transfer(self, doc: dict) -> dict:
        """The disaggregation verb (serve/disagg.py): this worker is
        the source (export_* kinds, prefill tier) or the sink
        (install_* kinds, decode tier) of one prefix transfer. State
        between kinds lives in the Local* adapters, lazily built —
        a worker that never disaggregates never touches them."""
        import numpy as np

        from .disagg import LocalPageSink, LocalPageSource
        from .rpc import page_block_to_wire
        if not hasattr(self, "_xfer_src"):
            self._xfer_src = LocalPageSource(self.engine)
            self._xfer_sink = LocalPageSink(self.engine)
        kind, key = doc["kind"], doc["key"]
        if kind == "export_begin":
            n = self._xfer_src.begin(
                key, np.asarray(doc["prompt"], np.int32),
                int(doc["from_page"]))
            return {"pages": n,
                    "page_bytes": self._xfer_src.page_bytes}
        if kind == "export_chunk":
            blocks, cursor, done = self._xfer_src.chunk(
                key, int(doc["cursor"]), int(doc.get("limit", 0)))
            return {"blocks": [page_block_to_wire(b) for b in blocks],
                    "cursor": cursor, "done": done}
        if kind == "export_end":
            self._xfer_src.end(key)
            return {}
        if kind == "install_begin":
            if self.draining:
                return {"accepted": False}
            return {"accepted": self._xfer_sink.begin(
                key, np.asarray(doc["prompt"], np.int32),
                int(doc["from_page"]), int(doc["n_pages"]))}
        if kind == "install_chunk":
            self._xfer_sink.chunk(key, doc["blocks"])
            return {}
        if kind == "install_commit":
            if doc.get("abort"):
                self._xfer_sink.abort(key)
                return {"registered": 0}
            return {"registered": self._xfer_sink.commit(key)}
        raise ValueError(f"unknown page_transfer kind {kind!r}")

    def op_health(self, doc: dict) -> dict:
        return {
            "pid": os.getpid(),
            "vocab_size": int(self.engine.cfg.vocab_size),
            "in_flight": self._in_flight_ids(),
            "replayed": self.n_replayed,
            "draining": self.draining,
            "counters": {k: int(v) for k, v in
                         self.engine.metrics.counters.items()},
            **self._gauges(),
        }

    def op_summary(self, doc: dict) -> dict:
        from .engine import engine_summary_block
        return {"block": engine_summary_block(self.engine)}

    def _journal_view(self) -> List[dict]:
        """Condensed journal state for ``journal_drain``: the last
        finish reason per id (in journal order), then the
        still-unfinished requests as wire docs. Computed fresh per
        drain — the file is worker-local and the reader is the shared
        torn-tail-tolerant one, so a drain racing an append sees a
        consistent prefix."""
        if self.journal is None:
            return []
        from ..utils.jsonl import load_jsonl_if_exists
        reasons: Dict[str, str] = {}
        for rec in load_jsonl_if_exists(self.journal.path):
            if rec.get("ev") == "finish":
                reasons[rec["id"]] = rec.get("reason", "")
        now = self.clock()
        return ([{"kind": "finished", "id": rid, "reason": reason}
                 for rid, reason in reasons.items()]
                + [{"kind": "unfinished",
                    "req": request_to_wire(req, now)}
                   for req in RequestJournal.unfinished(
                       self.journal.path)])

    def op_journal_drain(self, doc: dict) -> dict:
        """Stream the local journal's condensed state in bounded
        frames: the router pages with ``cursor`` until ``eof``. This
        replaces the shared-filesystem journal read PR 9's
        ``attach_replica`` did — reconciliation state crosses the RPC
        channel, so the worker's disk can live on another machine.

        The view is SNAPSHOTTED at ``cursor == 0`` and later frames
        page over that snapshot: one disk read per drain session (not
        per frame — a long journal would make reconcile O(R^2)), and
        a record appended mid-drain can never shift the paging under
        the reader. ``kinds`` filters the snapshot (the router's
        attach only needs the finish records; the unfinished half
        exists for a router rebuilding from nothing)."""
        cursor = max(int(doc.get("cursor", 0)), 0)
        limit = max(1, min(int(doc.get("limit", JOURNAL_DRAIN_LIMIT)),
                           JOURNAL_DRAIN_LIMIT))
        kinds = doc.get("kinds")
        if cursor == 0 or self._drain_snapshot is None:
            records = self._journal_view()
            if kinds:
                records = [r for r in records if r["kind"] in kinds]
            self._drain_snapshot = records
        records = self._drain_snapshot
        frame = records[cursor:cursor + limit]
        eof = cursor + len(frame) >= len(records)
        if eof:
            self._drain_snapshot = None
        return {"records": frame, "cursor": cursor + len(frame),
                "eof": eof}

    def op_drain(self, doc: dict) -> dict:
        """Rolling-restart drain: refuse new submits, cancel everything
        in flight as migrated (the journal records the finishes, so the
        NEXT incarnation's replay resurrects none of it)."""
        self.draining = True
        ids = self._in_flight_ids()
        for rid in list(self.engine.in_flight_ids()):
            self.engine.cancel(rid, migrated=True)
        for req in self._replay_pending:
            if self.journal is not None:
                self.journal.record_finish(req.id, FINISH_CANCELLED)
        self._replay_pending = []
        return {"cancelled": ids}

    def op_shutdown(self, doc: dict) -> dict:
        asyncio.get_running_loop().call_soon(self.stop_event.set)
        return {"stopping": True}


async def _register_attempt(router_addr: str, doc: dict) -> dict:
    """ONE register frame to the fleet's RpcListener. Returns the ok
    response; raises :class:`RpcProtocolError` on a typed rejection
    (a version/shape-mismatched build must exit, not retry) and
    :class:`ConnectionError` on transport failure or any other
    rejection (the caller owns the retry/backoff policy)."""
    host, _, port = router_addr.rpartition(":")
    writer = None
    try:
        reader, writer = await asyncio.open_connection(
            host or "127.0.0.1", int(port))
        writer.write(encode_frame({"op": "register", **doc}))
        await writer.drain()
        header = await asyncio.wait_for(
            reader.readexactly(HEADER_BYTES), 15.0)
        n, crc = decode_header(header)
        body = await asyncio.wait_for(reader.readexactly(n), 15.0)
        if not crc_ok(body, crc):
            raise ConnectionError(
                "registration response checksum mismatch")
        resp = json.loads(body)
    except RpcProtocolError:
        raise
    except (OSError, ValueError, asyncio.IncompleteReadError,
            asyncio.TimeoutError, ConnectionError) as e:
        raise ConnectionError(f"{type(e).__name__}: {e}") from e
    finally:
        if writer is not None:
            writer.close()
    if resp.get("ok"):
        return resp
    if resp.get("kind") == "protocol":
        raise RpcProtocolError(resp.get("error", "protocol mismatch"))
    raise ConnectionError(resp.get("error", "rejected"))


async def _register_with_router(router_addr: str, doc: dict,
                                budget_s: float = 120.0) -> dict:
    """Startup registration: ``_register_attempt`` retried until the
    listener answers (it polls from the router's single-threaded loop,
    so the response may lag a tick). Transport failures retry;
    :class:`RpcProtocolError` propagates — a mismatched build exits."""
    deadline = time.monotonic() + budget_s
    last = "no attempt"
    while time.monotonic() < deadline:
        try:
            return await _register_attempt(router_addr, doc)
        except ConnectionError as e:
            last = str(e)
        await asyncio.sleep(0.2)
    raise RuntimeError(
        f"registration with {router_addr} failed: {last}")


async def _reregister_loop(worker, router_addr: str, doc: dict,
                           idle_s: float = REREGISTER_IDLE_S,
                           backoff_s: float = REREGISTER_BACKOFF_S,
                           backoff_cap_s: float =
                           REREGISTER_BACKOFF_CAP_S,
                           on_reregister=None, rng=None) -> None:
    """Keep the worker attached across router restarts (ROADMAP 3a
    remainder): the startup handshake registered exactly once, so a
    router whose listener restarted (or whose process was replaced —
    it recovers in-flight work from its OWN ledger, never worker disk)
    would simply never drive this worker again. This loop watches for
    SILENCE — no inbound RPC for ``idle_s`` — and re-sends the
    register frame until a listener answers; re-registering at the
    same gen is an idempotent re-attach on the supervisor side. A
    typed protocol rejection stops the worker (the fleet's expected
    shape changed under us — serving on would split streams).

    Backoff is FULL-JITTER exponential (``uniform(0, min(cap, base *
    2^n))``): plain doubling is synchronized across the fleet — every
    worker detects a partition heal on the same idle tick and the
    whole fleet re-registers against the router in one thundering
    herd, exactly when the router is busiest reconciling. The jitter
    decorrelates them; ``rng`` is injectable for deterministic tests
    and seeds from the pid otherwise (each process must draw a
    DIFFERENT schedule — that is the point).

    One SILENCE EPISODE is one logical registration: the idem key on
    the register frame is refreshed when a new episode begins and
    reused across the retries within it, so a listener that executed
    the attach but lost the response answers the retry from its reply
    cache instead of reconciling twice."""
    rng = rng or random.Random(os.getpid())
    attempt = 0
    episode = 0
    in_episode = False
    base_idem = doc.get("idem", f"reg.{doc.get('worker_idx', 0)}"
                                f".{doc.get('gen', 0)}")
    while not worker.stop_event.is_set():
        if time.monotonic() - worker.last_contact < idle_s:
            # healthy traffic: reset the backoff and poll at half the
            # idle threshold so silence is detected promptly
            attempt = 0
            in_episode = False
            await asyncio.sleep(idle_s / 2)
            continue
        if not in_episode:
            in_episode = True
            episode += 1
            doc = {**doc, "idem": f"{base_idem}.re{episode}"}
        try:
            await _register_attempt(router_addr, doc)
            worker.last_contact = time.monotonic()
            attempt = 0
            in_episode = False
            if on_reregister is not None:
                on_reregister()
        except RpcProtocolError as e:
            print(f"re-registration REJECTED (protocol/shape "
                  f"mismatch): {e}; stopping", file=sys.stderr)
            worker.stop_event.set()
            return
        except ConnectionError:
            attempt += 1
        # attempts are spaced by the full-jitter backoff (not the idle
        # poll), so a long outage decays toward uniform draws over
        # [0, cap) — decorrelated across the fleet
        await asyncio.sleep(rng.uniform(
            0.0, min(backoff_cap_s, backoff_s * (2.0 ** attempt))))


def warm_engine(engine: Engine) -> None:
    """One throwaway greedy request through prefill + decode, so
    readiness implies compiled programs (no journal attached yet — a
    warmup request must never appear in a crash journal). With a
    decode window configured the bucketed window programs compiled at
    engine construction (``Engine._warm_windows``); this request is
    long enough to EXERCISE the steady-state path past the admission
    boundary's mixed dispatch (``EngineConfig.warmup_tokens`` — shared
    with the replay warmup)."""
    import numpy as np

    from .requests import SamplingParams
    engine.submit(Request(id="__warmup__",
                          prompt=np.zeros((1,), np.int32),
                          max_new_tokens=engine.ecfg.warmup_tokens(),
                          sampling=SamplingParams(greedy=True)))
    engine.drain()


async def _run_async(worker: WorkerServer, host: str, port: int,
                     router_addr: Optional[str], gen: int,
                     worker_idx: int, shape_hash: str,
                     tier: str = "mixed") -> int:
    # arm the wire-level generation fence: dispatch() rejects calls
    # stamped with any OTHER incarnation's gen (see WorkerServer.gen)
    worker.gen = gen
    server = await asyncio.start_server(
        lambda r, w: serve_connection(r, w, worker.dispatch),
        host, port)
    bound = server.sockets[0].getsockname()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            loop.add_signal_handler(sig, worker.stop_event.set)
        except NotImplementedError:   # non-Unix event loops
            pass
    print(f"worker listening on {bound[0]}:{bound[1]} "
          f"pid={os.getpid()} gen={gen} idx={worker_idx} "
          f"shape={shape_hash} replayed={worker.n_replayed}",
          file=sys.stderr)
    rereg_task = None
    if router_addr:
        # the server is ALREADY live: the supervisor's attach
        # (health/stream_drain/journal_drain RPCs) is served by this
        # same loop while the register coroutine awaits its response
        # "tier" advertises this worker's role in a disaggregated
        # fleet (serve/disagg.py): "prefill" takes prefill_only
        # requests, "decode" takes sessions, "mixed" takes both —
        # the router's placement policy reads it off registration
        # the idem key makes registration safe to blind-retry: a
        # supervisor that executed the attach but lost the response
        # answers the retry from its reply cache (one episode = one
        # logical attach; _reregister_loop refreshes the suffix per
        # silence episode)
        import jax
        devs = jax.devices()
        reg_doc = {"port": bound[1], "pid": os.getpid(), "gen": gen,
                   "worker_idx": worker_idx,
                   # the parent never initializes a backend (one
                   # process per chip): this is how it learns the
                   # fleet's device
                   "device": {"platform": devs[0].platform,
                              "kind": devs[0].device_kind,
                              "count": len(devs)},
                   "replayed": worker.n_replayed,
                   "proto": PROTO_VERSION, "shape_hash": shape_hash,
                   "tier": tier,
                   "page_size": int(worker.engine.pool.page_size),
                   "idem": f"reg.{worker_idx}.{gen}.{os.getpid()}.0"}
        try:
            await _register_with_router(router_addr, reg_doc)
        except RpcProtocolError as e:
            print(f"registration REJECTED (protocol/shape mismatch): "
                  f"{e}", file=sys.stderr)
            server.close()
            await server.wait_closed()
            return 3
        print(f"registered with {router_addr}", file=sys.stderr)
        worker.last_contact = time.monotonic()
        # registration is no longer once-at-startup: the background
        # loop re-announces this worker (bounded backoff) whenever the
        # router goes silent — a RESTARTED router's fresh listener
        # re-attaches us without an operator touching the worker
        rereg_task = asyncio.ensure_future(_reregister_loop(
            worker, router_addr, reg_doc,
            idle_s=getattr(worker, "reregister_idle_s",
                           REREGISTER_IDLE_S),
            on_reregister=lambda: print(
                f"re-registered with {router_addr} (router was "
                f"silent)", file=sys.stderr)))
    await worker.stop_event.wait()
    if rereg_task is not None:
        rereg_task.cancel()
        try:
            await rereg_task
        except asyncio.CancelledError:
            pass
    server.close()
    await server.wait_closed()
    # let an in-flight shutdown response flush before the process exits
    await asyncio.sleep(0.05)
    return 0


def run_worker(args) -> int:
    """The serve-worker subcommand body (see cli.py for the flags)."""
    from ..config import config_from_args
    from ..train.state import create_train_state
    from ..utils.compile_cache import enable_compile_cache
    import jax

    enable_compile_cache()
    cfg = config_from_args(args)
    state = create_train_state(jax.random.PRNGKey(cfg.train.seed),
                               cfg.model, cfg.train)
    if args.checkpoint_dir:
        from ..train.checkpoint import CheckpointManager
        restored = (CheckpointManager(args.checkpoint_dir)
                    .restore_latest(state))
        if restored is None:
            print("no checkpoint found; serving random init",
                  file=sys.stderr)
        else:
            state = restored
    # ONE EngineConfig builder with the router process (cli.py): the
    # multiproc forwarding contract (ENGINE_FORWARD_FLAGS) holds only
    # if both sides parse the same flags into the same config — a
    # worker owning its own --mesh-shape slice included
    from ..cli import engine_config_from_args
    ecfg = engine_config_from_args(args)
    if ecfg.weight_quant != "none":
        # serialized-calibration workflow (quant/weights.py): reuse
        # the scales next to the checkpoint so every worker in the
        # fleet serves the SAME quantized weights bit-for-bit
        from ..quant.weights import prepare_params
        state = state._replace(params=prepare_params(
            state.params, cfg.model, ecfg.weight_quant,
            checkpoint_dir=args.checkpoint_dir,
            log=lambda m: print(m, file=sys.stderr)))
    engine = Engine(state.params, cfg.model, ecfg)
    warm_engine(engine)

    journal = None
    if args.journal:
        journal = RequestJournal(args.journal,
                                 fsync_finish=not args.no_fsync,
                                 lock=True)
        engine.journal = journal
    worker = WorkerServer(engine, journal)
    worker.reregister_idle_s = getattr(args, "reregister_idle_s", 5.0)
    worker.warmed = True
    if args.journal:
        n = worker.replay_journal(args.journal)
        if n:
            print(f"journal replay: {n} unfinished request(s) "
                  f"resubmitted", file=sys.stderr)
    from .rpc import engine_shape_hash
    shape = engine_shape_hash(cfg.model, ecfg)
    try:
        return asyncio.run(_run_async(
            worker, args.host, args.port, args.router_addr, args.gen,
            args.worker_idx, shape,
            tier=getattr(args, "tier", "mixed")))
    finally:
        if journal is not None:
            journal.close()
