"""Process supervisor: RPC registration, restart-on-exit, backoff,
quarantine, rolling restarts, autoscaling — and real process/host-level
chaos for the multi-process fleet.

PR 4's supervision heals *inside* a process (rollback, watchdog,
shedding); PR 8's router heals *across* in-process replicas. This
module owns the replicas that are worker **processes**
(serve/worker.py): something must notice when one of them actually
dies, and something must decide how many of them there should BE. The
supervisor owns both policies; the router (serve/router.py) owns the
request ledger. The split is deliberate — the router decides what
happens to *requests* (keep waiting for a restart, requeue onto
survivors), the supervisor decides what happens to *processes*
(restart with backoff, give up and quarantine, spawn more under load,
drain the idle):

- **Registration over RPC**: the supervisor runs a poll-driven
  :class:`~..serve.rpc.RpcListener`; every spawned worker gets
  ``--router-addr`` and, once warmed + journal-replayed + bound, sends
  ONE ``register`` frame ``{port, pid, gen, worker_idx, replayed,
  proto, shape_hash}``. The handshake crosses the network, not a
  shared filesystem — no ready files — so a worker is placeable on
  any host that can reach the listener (an *unmanaged* worker
  registering with ``worker_idx=-1`` joins the fleet as a brand-new
  replica: start ``serve-worker --router-addr host:port`` anywhere).
  The handshake carries :data:`~..serve.rpc.PROTO_VERSION` and
  :func:`~..serve.rpc.engine_shape_hash`; a mismatched worker build is
  rejected with a typed :class:`~..serve.rpc.RpcProtocolError` at
  registration — exit code 3, never a codec drift mid-traffic. The
  fleet's expected shape is pinned by config
  (``SupervisorConfig.expect_shape_hash``) or by the first successful
  registration.
- **Death detection**: ``Popen.poll`` per tick, plus periodic RPC
  ``health`` probes with short timeouts (a zombie that holds its port
  but answers nothing is as dead as an exited one — two consecutive
  probe failures escalate to SIGKILL so the exit path takes over).
- **Restart-on-exit**: an unexpected exit marks the replica down in
  the router (its in-flight ledger entries WAIT — the restarted worker
  replays its journal and resumes them), then respawns after an
  exponential backoff (``backoff_s * backoff_mult^n``). Each spawn
  carries a fresh generation; the supervisor attaches the router only
  on the registration message showing the generation it launched.
- **Restart budget → quarantine**: past ``restart_budget`` *crash*
  restarts (intentional rolling-restart stops are free), the
  supervisor stops trying: ``Router.abandon_replica`` requeues the
  worker's in-flight work onto the survivors (from the router's OWN
  ledger — the dead worker's disk is never read) and the replica
  leaves rotation for good.
- **Rolling restart**: replica by replica — drain (the router
  migrates its in-flight requests onto the rest of the fleet), stop
  gracefully (``shutdown`` RPC, SIGTERM fallback), respawn, wait
  registered+attached, move on. At least ``n-1`` workers serve at
  every moment, so a fleet of two or more drops nothing; ``/readyz``
  reports 503 exactly when zero routable warmed workers remain.
- **Autoscaling** (:class:`AutoscaleConfig` + a ``spec_factory``):
  the supervisor reads the offered-load/occupancy gauges the router
  already exports (``Router.offered_load``) every tick. Sustained
  backlog (queued work above ``up_backlog_per_worker`` per routable
  worker for ``up_patience`` ticks) spawns a fresh worker — it warms,
  registers, attaches, takes traffic, zero recompiles for anyone else.
  A sustained lull (empty queues, occupancy the smaller fleet can
  hold, ``down_patience`` ticks) retires the highest-index worker
  through the SAME drain→shutdown path a rolling restart uses — its
  in-flight work migrates, it exits, and it is NOT respawned
  (``RETIRED``). Scale actions are ``cooldown_ticks`` apart, bounded
  by ``[min_workers, max_workers]``. A rolling restart is therefore
  just the degenerate deploy: drain→respawn instead of drain→retire.
- **Chaos**: ``proc_kill`` (a real ``SIGKILL``), ``proc_hang``
  (``SIGSTOP`` for N ticks), and ``host_loss`` — SIGKILL **plus
  deletion of the worker's whole working directory, crash journal
  included**: the spot-VM/TPU-preemption scenario where the machine is
  gone, not just the process. The respawned worker replays nothing;
  recovery is the router's own request ledger. All three arrive
  through the standard ``FaultPlan`` machinery (``fleet/step`` —
  faults/fleet.py).

Everything is ticked from the same single-threaded loop that steps the
router (the HTTP driver task, or the fleet replay loop): one
``supervisor.tick()`` after each ``router.step()``. No threads, no
signals-as-control-flow — deaths are observed, never raced; the
registration listener is polled, never awaited.
"""

from __future__ import annotations

import os
import shutil
import signal
import subprocess
import sys
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Callable, List, Optional

# NOTE: serve.* imports stay function-local in this module — importing
# the serve package pulls jax, and the supervisor must stay importable
# from jax-free contexts (unit tests over stub routers included)

#: handle lifecycle states
RUNNING = "running"
BACKOFF = "backoff"
SPAWNING = "spawning"       # process launched, registration not seen yet
QUARANTINED = "quarantined"
STOPPED = "stopped"
RETIRED = "retired"         # scale-down complete: exited, not respawned


@dataclass(frozen=True)
class SupervisorConfig:
    """Restart policy knobs (docs/robustness.md has the fault matrix)."""

    #: crash restarts per worker before quarantine (intentional
    #: rolling-restart stops do not count)
    restart_budget: int = 3
    backoff_s: float = 0.5
    backoff_mult: float = 2.0
    #: a spawned worker must REGISTER within this budget (covers jax
    #: import + compile warmup) or the spawn counts as a crash
    ready_timeout_s: float = 180.0
    #: RPC health-probe budget; two consecutive failures escalate to
    #: SIGKILL
    probe_timeout_s: float = 2.0
    #: probe every N ticks (0 disables probing — the router's own step
    #: RPC failures still catch deaths)
    probe_every: int = 8
    #: required engine_shape_hash for registering workers; None = the
    #: first successful registration pins the fleet's shape, and every
    #: later worker must match it (RpcProtocolError otherwise)
    expect_shape_hash: Optional[str] = None


@dataclass(frozen=True)
class AutoscaleConfig:
    """Elastic fleet sizing from the router's own gauges. The
    supervisor reads ``Router.offered_load()`` once per tick; patience
    and cooldown are in ticks (one tick per router step), so decisions
    are as deterministic as the replay driving them."""

    min_workers: int = 1
    max_workers: int = 4
    #: queued work per routable worker that counts as sustained
    #: backlog (scale-up pressure)
    up_backlog_per_worker: float = 2.0
    up_patience: int = 4
    #: scale down only when queues are empty AND the active slots
    #: would fit the remaining workers at this per-worker occupancy
    down_active_per_worker: float = 1.0
    down_patience: int = 32
    #: minimum ticks between scale actions (a fresh worker must get a
    #: chance to absorb load before the next decision)
    cooldown_ticks: int = 32


@dataclass
class WorkerSpec:
    """How to (re)launch one worker. ``cmd`` is the full command minus
    the per-spawn ``--gen``/``--worker-idx``/``--router-addr``; the
    supervisor appends those. ``workdir`` is the worker's PRIVATE
    directory (journal + log) — nothing else ever reads it; host_loss
    chaos deletes it wholesale."""

    idx: int
    cmd: List[str]
    journal_path: str
    workdir: Optional[str] = None
    log_path: Optional[str] = None
    env: Optional[dict] = None


@dataclass
class WorkerHandle:
    spec: WorkerSpec
    proc: Optional[subprocess.Popen] = None
    state: str = STOPPED
    gen: int = -1
    pid: Optional[int] = None
    restarts: int = 0          # every respawn (rolling included)
    crash_restarts: int = 0    # budget-counted respawns
    backoff_until: float = 0.0
    spawn_t: float = 0.0
    hang_ticks: int = 0        # SIGSTOP chaos: SIGCONT when it hits 0
    probe_failures: int = 0
    intentional_stop: bool = False
    retiring: bool = False     # scale-down in progress: exit → RETIRED
    #: the device this worker registered with ({"platform", "kind",
    #: "count"}) — how a parent that holds no chip (one process per
    #: chip) learns what its fleet runs on
    device: Optional[dict] = None
    events: List[str] = field(default_factory=list)


class ProcSupervisor:
    """Owns the worker processes of one fleet. Drive it with
    :meth:`tick` from the router's loop; it talks back to the router
    through ``mark_down`` / ``attach_replica`` / ``abandon_replica`` /
    ``add_replica`` / ``offered_load``.
    """

    def __init__(self, specs: List[WorkerSpec],
                 cfg: SupervisorConfig = SupervisorConfig(),
                 autoscale: Optional[AutoscaleConfig] = None,
                 spec_factory: Optional[
                     Callable[[int], WorkerSpec]] = None,
                 listen_host: str = "127.0.0.1"):
        self.cfg = cfg
        self.autoscale = autoscale
        self.spec_factory = spec_factory
        self.handles = [WorkerHandle(spec=s) for s in specs]
        self.router = None          # attach_router
        self.ticks = 0
        self._rolling: List[int] = []
        self._rolling_phase = ""
        self._rolling_target_gen = -1
        self.events: List[str] = []
        #: the registration endpoint every worker handshakes with
        #: (--router-addr); polled from tick()/start_all(), never blocks
        from ..serve.rpc import RpcListener
        self.listener = RpcListener(host=listen_host)
        self.expect_shape_hash = cfg.expect_shape_hash
        #: replica indices of unmanaged workers that registered from
        #: outside (no handle, no restart policy — their host owns that)
        self.external: List[int] = []
        self.scale_ups = 0
        self.scale_downs = 0
        #: most workers ever provisioned CONCURRENTLY (scale-downs
        #: between scale-ups don't inflate it — the honest elasticity
        #: peak for the bench artifact)
        self.peak_workers = len(specs)
        self._up_streak = 0
        self._down_streak = 0
        self._last_scale_tick = 0
        #: registration reply cache, keyed by the worker's idem key: a
        #: duplicated or blind-retried register frame is answered with
        #: the ORIGINAL attach response instead of reconciling the
        #: ledger twice (serve/rpc.py idempotency contract, GL024)
        self._reg_replies: "OrderedDict[str, dict]" = OrderedDict()

    def attach_router(self, router) -> None:
        self.router = router
        router.supervisor = self

    @property
    def router_addr(self) -> str:
        """host:port workers register with (the --router-addr value)."""
        return self.listener.addr

    @property
    def reviving(self) -> bool:
        """True while any worker is on its way back (spawning, backing
        off, or intentionally stopped for a rolling restart) — the
        router's requeue ladder holds its retry budget while this is
        set instead of burning attempts against a fleet that is mid-
        recovery (a zero-routable window during a single-worker rolling
        restart must not reject the held requests). A RETIRING worker
        is leaving on purpose and does not count."""
        return any(h.state in (SPAWNING, BACKOFF)
                   or (h.intentional_stop and not h.retiring)
                   for h in self.handles)

    def _handle(self, idx: int) -> Optional[WorkerHandle]:
        """Handle by WORKER INDEX (== router replica index). Position
        in ``handles`` no longer equals the index once external
        replicas joined the router between scale-ups."""
        for h in self.handles:
            if h.spec.idx == idx:
                return h
        return None

    # ------------------------------------------------------------- spawn

    def _event(self, msg: str) -> None:
        self.events.append(msg)
        if len(self.events) > 256:
            del self.events[:len(self.events) - 256]
        if self.router is not None:
            from ..utils.telemetry import ROUTER_TRACK
            self.router._event(f"supervisor: {msg}")
            self.router.tel.instant("supervisor", ROUTER_TRACK,
                                    note=msg)

    def _spawn(self, h: WorkerHandle) -> None:
        h.gen += 1
        h.restarts += int(h.gen > 0)
        if h.spec.workdir:
            # host_loss chaos deletes the whole workdir; a respawn is
            # the replacement host coming up with an empty disk
            os.makedirs(h.spec.workdir, exist_ok=True)
        stdout = subprocess.DEVNULL
        if h.spec.log_path:
            os.makedirs(os.path.dirname(h.spec.log_path) or ".",
                        exist_ok=True)
            stdout = open(h.spec.log_path, "a")
        env = {**os.environ, **(h.spec.env or {})}
        h.proc = subprocess.Popen(
            h.spec.cmd + ["--gen", str(h.gen),
                          "--worker-idx", str(h.spec.idx),
                          "--router-addr", self.router_addr],
            stdout=stdout, stderr=stdout, env=env)
        if stdout is not subprocess.DEVNULL:
            stdout.close()      # Popen holds its own dup
        h.pid = h.proc.pid
        h.state = SPAWNING
        h.spawn_t = time.monotonic()
        h.probe_failures = 0
        self._event(f"worker {h.spec.idx} spawned "
                    f"(pid {h.pid}, gen {h.gen})")

    # ------------------------------------------------------ registration

    def _handle_register(self, doc: dict, peer_host: str) -> dict:
        """The RpcListener handler: validate the handshake, attach the
        router. Raising :class:`RpcProtocolError` answers the worker
        with ``kind="protocol"`` — its client raises the typed error
        and the worker exits 3 instead of retrying.

        Registration MUTATES the router (attach reconciliation), so it
        carries an idempotency key like the other mutating verbs: a
        worker that registered but lost the response blind-retries the
        same frame, and the reply cache answers it with the original
        attach result instead of reconciling twice. Rejections are NOT
        cached — a retried bad handshake must re-validate."""
        idem = doc.get("idem")
        if idem is not None and idem in self._reg_replies:
            return {**self._reg_replies[idem], "idem_hit": True}
        resp = self._register_attach(doc, peer_host)
        if idem is not None:
            self._reg_replies[idem] = resp
            while len(self._reg_replies) > 64:
                self._reg_replies.popitem(last=False)
        return resp

    def _register_attach(self, doc: dict, peer_host: str) -> dict:
        from ..serve.rpc import PROTO_VERSION, RpcProtocolError
        router = self.router
        assert router is not None, "attach_router first"
        proto = int(doc.get("proto", -1))
        if proto != PROTO_VERSION:
            raise RpcProtocolError(
                f"worker speaks protocol v{proto}, router v"
                f"{PROTO_VERSION} — rebuild the worker")
        shape = str(doc.get("shape_hash", ""))
        if self.expect_shape_hash is None:
            # first successful registration pins the fleet's shape
            self.expect_shape_hash = shape
        elif shape != self.expect_shape_hash:
            raise RpcProtocolError(
                f"worker engine shape {shape} != fleet "
                f"{self.expect_shape_hash} — a different model or "
                f"engine build cannot join this fleet")
        idx = int(doc.get("worker_idx", -1))
        gen = int(doc.get("gen", 0))
        port = int(doc["port"])
        pid = int(doc.get("pid", 0))
        # disaggregation role + page geometry (serve/disagg.py): the
        # worker advertises both; older workers default to the
        # colocated "mixed" role
        tier = str(doc.get("tier", "mixed"))
        page_size = int(doc.get("page_size", 0))
        h = self._handle(idx) if idx >= 0 else None
        if h is not None:
            if gen != h.gen:
                # a stale incarnation (pre-restart straggler) — its
                # replacement is the one the supervisor launched
                raise ValueError(
                    f"stale generation {gen} (current {h.gen})")
            info = router.attach_replica(idx, port, pid=pid, gen=gen,
                                         host=peer_host, tier=tier,
                                         page_size=page_size)
            router.replicas[idx].restarts = h.restarts
            h.state = RUNNING
            h.pid = pid
            h.device = doc.get("device")
            h.probe_failures = 0
            self._event(f"worker {idx} registered+attached "
                        f"(gen {gen}, host {peer_host}, "
                        f"kept {info['kept']}, "
                        f"requeued {info['requeued']}, "
                        f"ghosts {info['ghosts']})")
            return {"idx": idx, **info}
        # an UNMANAGED worker joining from anywhere: grow the fleet.
        # No handle — its lifecycle belongs to whoever spawned it; the
        # router's step-RPC failures still mark it down if it vanishes.
        from ..serve.router import RemoteReplica
        new_idx = len(router.replicas)
        rep = RemoteReplica(
            new_idx, None, host=peer_host,
            rpc_timeout_s=router.rcfg.step_timeout_s,
            step_timeout_s=router.rcfg.step_timeout_s)
        router.add_replica(rep)
        info = router.attach_replica(new_idx, port, pid=pid, gen=gen,
                                     host=peer_host, tier=tier,
                                     page_size=page_size)
        self.external.append(new_idx)
        self._event(f"external worker joined as replica {new_idx} "
                    f"(host {peer_host}, pid {pid})")
        return {"idx": new_idx, **info}

    def _poll_registrations(self) -> int:
        return self.listener.poll(self._handle_register)

    def start_all(self, wait: bool = True,
                  timeout_s: Optional[float] = None) -> None:
        """Spawn every worker; with ``wait`` (the default), block until
        each one registered and attached to the router. A failed (or
        interrupted) startup stops EVERY spawned worker before raising
        — an orphaned worker would hold its journal flock and crash-
        loop the next run's replacement with JournalBusyError."""
        for h in self.handles:
            self._spawn(h)
        if not wait:
            return
        budget = timeout_s or self.cfg.ready_timeout_s
        deadline = time.monotonic() + budget
        try:
            while time.monotonic() < deadline:
                self._poll_registrations()
                for h in self.handles:
                    if h.state == SPAWNING:
                        self._check_spawn(h)
                    elif (h.state == BACKOFF
                          and time.monotonic() >= h.backoff_until):
                        # a worker that crashed during startup retries
                        # inside the wait (the tick loop is not running
                        # yet) — without this, one startup crash burns
                        # the whole ready budget
                        self._spawn(h)
                if all(h.state == RUNNING for h in self.handles):
                    return
                if any(h.state == QUARANTINED for h in self.handles):
                    break          # crash-looped out of the budget:
                    #                fail fast, don't burn the deadline
                time.sleep(0.05)
        except BaseException:      # Ctrl-C mid-warmup included
            self.stop_all()
            raise
        bad = [h.spec.idx for h in self.handles if h.state != RUNNING]
        logs = [self._handle(i).spec.log_path for i in bad]
        self.stop_all()
        raise RuntimeError(
            f"workers {bad} not ready within {budget}s (see {logs})")

    def stop_all(self, timeout_s: float = 15.0) -> None:
        for h in self.handles:
            h.intentional_stop = True
            h.retiring = False
            h.state = STOPPED
            if h.proc is not None and h.proc.poll() is None:
                if h.hang_ticks:          # a stopped process cannot
                    self._signal(h, signal.SIGCONT)   # handle SIGTERM
                    h.hang_ticks = 0
                self._signal(h, signal.SIGTERM)
        deadline = time.monotonic() + timeout_s
        for h in self.handles:
            if h.proc is None:
                continue
            while (h.proc.poll() is None
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            if h.proc.poll() is None:
                self._signal(h, signal.SIGKILL)
                h.proc.wait()
        self.listener.close()

    @staticmethod
    def _signal(h: WorkerHandle, sig) -> None:
        try:
            os.kill(h.proc.pid, sig)
        except (OSError, AttributeError):
            pass

    # -------------------------------------------------------------- tick

    def tick(self) -> None:
        """One supervision pass: serve pending registrations, resume
        chaos hangs, observe deaths, advance backoffs/spawns, probe
        health, advance any rolling restart, make the autoscale
        decision. Call after every ``router.step()`` (and on idle loop
        iterations — restarts must progress while the fleet waits)."""
        router = self.router
        assert router is not None, "attach_router first"
        self.ticks += 1
        self._poll_registrations()
        for h in self.handles:
            if h.hang_ticks > 0:
                h.hang_ticks -= 1
                if h.hang_ticks == 0:
                    self._signal(h, signal.SIGCONT)
                    self._event(f"worker {h.spec.idx} SIGCONT "
                                f"(hang over)")
            if h.state == RUNNING:
                if h.proc is not None and h.proc.poll() is not None:
                    self._on_exit(h, h.proc.returncode)
                    continue
                self._maybe_probe(h)
                # the router declared it down (RPC refused / worker
                # dispatch broken) but the process lingers: a zombie —
                # SIGKILL it so the exit path owns recovery
                if (not router.replicas[h.spec.idx].alive
                        and h.hang_ticks == 0):
                    self._event(f"worker {h.spec.idx} unreachable but "
                                f"process alive — escalating SIGKILL")
                    self._signal(h, signal.SIGKILL)
            elif h.state == BACKOFF:
                if time.monotonic() >= h.backoff_until:
                    self._spawn(h)
            elif h.state == SPAWNING:
                self._check_spawn(h)
        self._tick_rolling()
        self._tick_autoscale()

    def _on_exit(self, h: WorkerHandle, rc) -> None:
        router = self.router
        router.mark_down(h.spec.idx,
                         f"process exited rc={rc}")
        if h.intentional_stop:
            h.intentional_stop = False
            if h.retiring:
                # scale-down complete: drained, stopped, NOT respawned
                h.retiring = False
                h.state = RETIRED
                self._event(f"worker {h.spec.idx} retired "
                            f"(scale-down complete)")
                return
            # rolling restart / operator stop: free respawn, no budget
            self._event(f"worker {h.spec.idx} stopped (intentional); "
                        f"respawning")
            self._spawn(h)
            return
        h.crash_restarts += 1
        if h.crash_restarts > self.cfg.restart_budget:
            h.state = QUARANTINED
            self._event(f"worker {h.spec.idx} exceeded restart budget "
                        f"({self.cfg.restart_budget}); quarantined — "
                        f"requeueing its in-flight work onto survivors")
            router.abandon_replica(h.spec.idx)
            return
        delay = (self.cfg.backoff_s
                 * self.cfg.backoff_mult ** (h.crash_restarts - 1))
        h.state = BACKOFF
        h.backoff_until = time.monotonic() + delay
        self._event(f"worker {h.spec.idx} died rc={rc}; restart "
                    f"{h.crash_restarts}/{self.cfg.restart_budget} in "
                    f"{delay:.2f}s")

    def _check_spawn(self, h: WorkerHandle) -> None:
        """A SPAWNING worker either registers (the listener handler
        flips it RUNNING), dies (fold into the crash path), or blows
        the ready budget (SIGKILL so the exit path takes over)."""
        if h.proc is not None and h.proc.poll() is not None:
            # died during startup — counts as a crash
            h.state = RUNNING   # route through the common exit path
            self._on_exit(h, h.proc.returncode)
            return
        if (time.monotonic() - h.spawn_t
                > self.cfg.ready_timeout_s):
            self._event(f"worker {h.spec.idx} missed ready deadline; "
                        f"killing")
            self._signal(h, signal.SIGKILL)

    def _maybe_probe(self, h: WorkerHandle) -> None:
        if (self.cfg.probe_every <= 0
                or self.ticks % self.cfg.probe_every
                or h.hang_ticks > 0):   # a chaos-hung worker is
            return                      # *supposed* to be unresponsive
        rep = self.router.replicas[h.spec.idx]
        try:
            rep.client.call("health",
                            timeout_s=self.cfg.probe_timeout_s)
            h.probe_failures = 0
        except Exception:  # noqa: BLE001 — timeout, refusal, garbage:
            # the probe only counts failures, the escalation decides
            h.probe_failures += 1
            if h.probe_failures >= 2:
                self._event(f"worker {h.spec.idx} failed "
                            f"{h.probe_failures} health probes; "
                            f"escalating SIGKILL")
                self._signal(h, signal.SIGKILL)

    # --------------------------------------------------------- autoscale

    def _tick_autoscale(self) -> None:
        """The elasticity decision, one per tick: read the router's
        offered-load gauges, track sustained pressure either way, act
        at patience through the SAME spawn/drain paths restarts use."""
        a = self.autoscale
        if a is None or self.spec_factory is None or self._rolling:
            return
        provisioned = [h for h in self.handles
                       if not h.retiring
                       and h.state in (RUNNING, SPAWNING, BACKOFF)]
        if any(h.state == SPAWNING for h in provisioned):
            return              # let the in-flight scale-up land first
        load = self.router.offered_load()
        n_routable = load["n_routable"]
        if self.ticks - self._last_scale_tick < a.cooldown_ticks:
            return
        if (load["queued"]
                > a.up_backlog_per_worker * max(n_routable, 1)):
            self._down_streak = 0
            self._up_streak += 1
            if (self._up_streak >= a.up_patience
                    and len(provisioned) < a.max_workers):
                self.scale_up()
        elif (load["queued"] == 0
              and n_routable > 1
              and len(provisioned) > a.min_workers
              and load["active"] <= (a.down_active_per_worker
                                     * (n_routable - 1))):
            self._up_streak = 0
            self._down_streak += 1
            if self._down_streak >= a.down_patience:
                self.scale_down()
        else:
            self._up_streak = self._down_streak = 0

    def scale_up(self) -> int:
        """Grow the fleet by one worker: a fresh spec from the
        factory, a fresh router replica slot, a normal spawn — it
        warms itself, registers, attaches, takes traffic."""
        assert self.spec_factory is not None, "no spec_factory"
        from ..serve.router import RemoteReplica
        router = self.router
        idx = len(router.replicas)
        spec = self.spec_factory(idx)
        spec.idx = idx
        h = WorkerHandle(spec=spec)
        self.handles.append(h)
        router.add_replica(RemoteReplica(
            idx, None,
            rpc_timeout_s=router.rcfg.step_timeout_s,
            step_timeout_s=router.rcfg.step_timeout_s))
        self.scale_ups += 1
        self._last_scale_tick = self.ticks
        self._up_streak = self._down_streak = 0
        self.router.metrics.inc("fleet_scale_ups")
        self._event(f"autoscale: scale-UP — spawning worker {idx} "
                    f"(sustained backlog)")
        self._spawn(h)
        self.peak_workers = max(self.peak_workers, sum(
            1 for x in self.handles
            if not x.retiring and x.state in (RUNNING, SPAWNING,
                                              BACKOFF)))
        return idx

    def scale_down(self) -> Optional[int]:
        """Shrink the fleet by one worker through the rolling-restart
        drain path: the router migrates its in-flight work, the worker
        journals + exits, and the exit is terminal (RETIRED) instead
        of a respawn. Zero requests drop — that is the whole point of
        reusing the drain."""
        victims = [h for h in self.handles
                   if h.state == RUNNING and not h.retiring
                   and not h.intentional_stop]
        if not victims:
            return None
        h = victims[-1]            # highest index leaves first (LIFO)
        idx = h.spec.idx
        h.retiring = True
        h.intentional_stop = True
        self.scale_downs += 1
        self._last_scale_tick = self.ticks
        self._up_streak = self._down_streak = 0
        self.router.metrics.inc("fleet_scale_downs")
        self.router.drain_replica(idx)
        rep = self.router.replicas[idx]
        try:
            rep.client.call("drain", timeout_s=2.0)
            rep.client.call("shutdown", timeout_s=2.0)
        except Exception:  # noqa: BLE001 — graceful path failed;
            # SIGTERM says the same thing louder
            self._signal(h, signal.SIGTERM)
        self._event(f"autoscale: scale-DOWN — draining worker {idx} "
                    f"(sustained lull)")
        return idx

    # ------------------------------------------------------------- chaos

    def chaos_kill(self, idx: int) -> None:
        """``proc_kill``: a real SIGKILL — no cleanup, no flushes."""
        h = self._handle(idx)
        if h is None:
            return
        self._event(f"CHAOS proc_kill worker {idx} (pid {h.pid})")
        self._signal(h, signal.SIGKILL)

    def chaos_hang(self, idx: int, ticks: int) -> None:
        """``proc_hang``: SIGSTOP now, SIGCONT after ``ticks`` ticks."""
        h = self._handle(idx)
        if h is None:
            return
        self._event(f"CHAOS proc_hang worker {idx} for {ticks} ticks")
        h.hang_ticks = max(int(ticks), 1)
        self._signal(h, signal.SIGSTOP)

    def chaos_host_loss(self, idx: int) -> None:
        """``host_loss``: the worker's MACHINE is gone — SIGKILL the
        process and delete its working directory, crash journal
        included. The respawn is the replacement host coming up with
        an empty disk: it replays nothing, and the router's own ledger
        is the only recovery there is (which is the property under
        test)."""
        h = self._handle(idx)
        if h is None:
            return
        self._event(f"CHAOS host_loss worker {idx} (pid {h.pid}; "
                    f"journal + workdir deleted)")
        if h.hang_ticks:
            h.hang_ticks = 0       # a SIGSTOPped process still dies
        self._signal(h, signal.SIGKILL)
        if h.proc is not None:
            try:
                h.proc.wait(timeout=10)
            except (subprocess.TimeoutExpired, OSError):
                pass
        # the host took its disk with it: journal, logs, everything
        if h.spec.workdir:
            shutil.rmtree(h.spec.workdir, ignore_errors=True)
        else:
            try:
                os.remove(h.spec.journal_path)
            except OSError:
                pass

    # --------------------------------------------------- rolling restart

    @property
    def rolling_active(self) -> bool:
        return bool(self._rolling)

    def start_rolling_restart(self) -> None:
        """Queue a drain -> stop -> respawn -> reattach cycle over every
        worker, one at a time (ticked forward by :meth:`tick`)."""
        if self._rolling:
            return
        self._rolling = [h.spec.idx for h in self.handles
                         if h.state not in (QUARANTINED, RETIRED)]
        self._rolling_phase = "drain"
        self._event(f"rolling restart of workers {self._rolling}")

    def _tick_rolling(self) -> None:
        if not self._rolling:
            return
        router = self.router
        idx = self._rolling[0]
        h = self._handle(idx)
        if h is None:
            self._rolling.pop(0)
            return
        if self._rolling_phase == "drain":
            router.drain_replica(idx)
            h.intentional_stop = True
            #: advance only once THIS generation is gone and the NEXT
            #: one is attached — "running and alive" is already true in
            #: the instant after the shutdown RPC (the worker takes a
            #: moment to exit), and advancing on it would drain the
            #: whole fleet at once
            self._rolling_target_gen = h.gen + 1
            rep = router.replicas[idx]
            try:
                rep.client.call("drain", timeout_s=2.0)
                rep.client.call("shutdown", timeout_s=2.0)
            except Exception:  # noqa: BLE001 — graceful path failed;
                # SIGTERM says the same thing louder
                self._signal(h, signal.SIGTERM)
            self._rolling_phase = "await_restart"
        elif self._rolling_phase == "await_restart":
            if (h.gen >= self._rolling_target_gen
                    and h.state == RUNNING
                    and router.replicas[idx].alive):
                self._rolling.pop(0)
                self._rolling_phase = "drain"
                if not self._rolling:
                    self._event("rolling restart complete")
            elif h.state == QUARANTINED:
                # it crashed its way out of the budget mid-restart —
                # abandon the rolling pass for this worker
                self._rolling.pop(0)
                self._rolling_phase = "drain"


# -------------------------------------------------------------- builders

def _worker_env(env: Optional[dict]) -> dict:
    """The workers must import THIS package regardless of the caller's
    cwd (`python -m` resolves against the child's sys.path, and the
    repo is not necessarily pip-installed)."""
    pkg_root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    env = dict(env or {})
    env.setdefault("PYTHONPATH", os.pathsep.join(
        p for p in (pkg_root, os.environ.get("PYTHONPATH")) if p))
    return env


def make_worker_spec(idx: int, workdir: str, config_args: List[str],
                     engine_args: Optional[List[str]] = None,
                     env: Optional[dict] = None,
                     tier: str = "mixed") -> WorkerSpec:
    """One ``serve-worker`` spec with a PRIVATE working directory
    (journal.jsonl + worker.log inside it). Nothing outside the worker
    process reads the directory — the router reconciles over RPC —
    and ``host_loss`` chaos deletes it wholesale. ``tier`` is the
    worker's disaggregation role (serve/disagg.py)."""
    os.makedirs(workdir, exist_ok=True)
    jpath = os.path.join(workdir, "journal.jsonl")
    log = os.path.join(workdir, "worker.log")
    cmd = [sys.executable, "-m", "replicatinggpt_tpu",
           "serve-worker", *config_args,
           "--port", "0", "--journal", jpath,
           *(["--tier", tier] if tier != "mixed" else []),
           *(engine_args or [])]
    return WorkerSpec(idx=idx, cmd=cmd, journal_path=jpath,
                      workdir=workdir, log_path=log,
                      env=_worker_env(env))


def make_worker_specs(n_workers: int, base_dir: str,
                      config_args: List[str],
                      engine_args: Optional[List[str]] = None,
                      env: Optional[dict] = None,
                      tiers: Optional[List[str]] = None
                      ) -> List[WorkerSpec]:
    """Specs for N ``serve-worker`` subprocesses, each in its own
    ISOLATED directory ``base_dir/worker{i}/`` — there is no shared
    journal directory anywhere in the fleet; ``base_dir`` is merely
    where this (single-machine) launcher happens to put the private
    dirs. ``config_args`` select the model (e.g. ``["--preset",
    "test-tiny"]``); ``engine_args`` are pool/page knobs; ``tiers``
    assigns a disaggregation role per worker (None = all mixed)."""
    if tiers is not None:
        assert len(tiers) == n_workers, (tiers, n_workers)
    return [make_worker_spec(
        i, os.path.join(base_dir, f"worker{i}"), config_args,
        engine_args, env,
        tier=(tiers[i] if tiers else "mixed"))
        for i in range(n_workers)]


def worker_spec_factory(base_dir: str, config_args: List[str],
                        engine_args: Optional[List[str]] = None,
                        env: Optional[dict] = None
                        ) -> Callable[[int], WorkerSpec]:
    """The autoscaler's spec source: ``factory(idx)`` yields a spec in
    a fresh private dir, same shape as the initial fleet's."""
    def factory(idx: int) -> WorkerSpec:
        return make_worker_spec(
            idx, os.path.join(base_dir, f"worker{idx}"), config_args,
            engine_args, env)
    return factory


def spawn_fleet(specs: List[WorkerSpec], rcfg=None, scfg=None,
                telemetry=None, clock=time.monotonic,
                wait: bool = True, autoscale=None, spec_factory=None,
                listen_host: str = "127.0.0.1"):
    """Launch the out-of-process fleet: one supervisor over ``specs``,
    one Router over :class:`~..serve.router.RemoteReplica` backends,
    wired together (``router.supervisor`` set, chaos delegated).
    Workers register over RPC — the router holds NO worker paths.
    Returns ``(router, supervisor)``; callers own shutdown
    (``supervisor.stop_all()`` then ``router.close()``)."""
    from ..serve.router import RemoteReplica, Router, RouterConfig
    rcfg = rcfg or RouterConfig(n_replicas=len(specs))
    scfg = scfg or SupervisorConfig()
    backends = [RemoteReplica(s.idx, None,
                              rpc_timeout_s=rcfg.step_timeout_s,
                              step_timeout_s=rcfg.step_timeout_s)
                for s in specs]
    router = Router(rcfg=rcfg, backends=backends, telemetry=telemetry,
                    clock=clock)
    sup = ProcSupervisor(specs, scfg, autoscale=autoscale,
                         spec_factory=spec_factory,
                         listen_host=listen_host)
    sup.attach_router(router)
    sup.start_all(wait=wait)
    return router, sup
