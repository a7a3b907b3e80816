"""Serving fleet membership: replica leases, piggybacked health, the agent.

The router tier (ISSUE 15) goes wide the way the master plane went elastic:
N `ServingServer` replicas (each possibly `--tp`) sit behind one router, and
every signal the router needs to dispatch — liveness, queue depth, free
pages, the load estimator's queue-wait figure, engine-restart count — rides
traffic that already flows, never a per-decision round trip ("RPC Considered
Harmful", PAPERS.md):

  * a replica REGISTERS with the router (`replica_register`, advertising its
    serving endpoint) and renews the lease with `replica_heartbeat` every
    lease/3, the heartbeat REQUEST carrying a load snapshot straight out of
    `ServingSession.stats()`;
  * the heartbeat REPLY carries the router's control signals back — a
    planned drain order, a "re-register" hint after an eviction the replica
    outlived — exactly the trick the resize drain signal uses on the master
    plane;
  * a WEDGED replica self-fences: the agent's heartbeat loop watches the
    session's progress marker, and an engine that has work but has made no
    progress past `stall_fence_s` (and is not inside a step — first-step jit
    compiles are not wedges) stops claiming liveness, so the router's lease
    expiry is the one arbiter of "alive" and a stalled-but-heartbeating
    replica cannot hold assignments hostage.

This module is the membership half: `Replica` (the router's view of one
replica), `FleetView` (lease + load bookkeeping — no RPCs live here, every
datum arrived piggybacked) and `ReplicaAgent` (the replica-side joiner).
The dispatch/failover/dedup machinery lives in serving/router.py."""

from __future__ import annotations

import logging
import threading
import time
import uuid
from typing import Any, Callable, Dict, List, Optional, Set, Tuple

from paddle_tpu.core import stats
from paddle_tpu.runtime.master import (
    EndpointsLike,
    MasterClient,
    parse_endpoints,
)

log = logging.getLogger("paddle_tpu.serving.fleet")

# the load-snapshot keys a replica heartbeat piggybacks (subset of
# ServingSession.stats()): everything the router's least-loaded choice,
# fleet-wide shed AND the autoscaler's pressure signals (cumulative shed /
# deadline-miss counters, ISSUE 17) reason about, nothing more — heartbeats
# stay small and the controller reads the whole fleet with zero new RPCs.
# `free_pages` is pages free NOW (ISSUE 34): a replica hands pages out as
# tokens are written, so it no longer counts what live requests will still
# ask for; no routing rule reads it differently for that.
LOAD_KEYS = (
    "queue_depth", "active_slots", "max_slots", "free_pages",
    "estimated_queue_wait_s", "engine_restarts", "decode_steps",
    "shed", "deadline_misses",
)


class ReplicaState:
    LIVE = "live"          # holding a lease, assignable
    DRAINING = "draining"  # planned drain: no new assignments, in-flight runs
    DRAINED = "drained"    # drain complete: deregistered cleanly
    EVICTED = "evicted"    # lease expired / connection dead: failed over
    CLOSED = "closed"      # pump shut down; terminal


class Replica:
    """The router's view of one ServingServer replica. All mutation happens
    under the owning Router's lock; this object is pure bookkeeping."""

    def __init__(self, replica_id: str, endpoint: Tuple[str, int],
                 index: int):
        self.replica_id = replica_id
        self.endpoint = (str(endpoint[0]), int(endpoint[1]))
        # registration order: the deterministic tie-break for assignment
        # scoring (replica ids carry a random prefix, so id order is not
        # stable across runs — tests and drills need stable placement)
        self.index = index
        self.state = ReplicaState.LIVE
        self.last_seen = time.monotonic()
        self.load: Dict[str, Any] = {}
        # fleet request ids whose DELIVERY the router still expects from
        # this replica (live assignments; hedging/failover bookkeeping)
        self.outstanding: Set[int] = set()
        # fleet rid -> replica-side rid for every request ever forwarded and
        # not yet answered/cancelled: survives eviction so the pump can keep
        # polling a partitioned replica and catch a LATE winner (which the
        # dedup map drops + counts) instead of going blind at the instant the
        # lease lapses
        self.rids: Dict[int, int] = {}
        self.assigned_total = 0
        self.failovers = 0
        self.late_results_dropped = 0
        self.conn_failures = 0
        # delta-poll cursors (ISSUE 16): fleet rid -> how many tokens of
        # that request THIS replica has already sent us, so each pump cycle
        # re-reads only the unseen suffix. Keyed per replica (a failover
        # target starts at 0 and re-sends the full mirror) and dropped with
        # the rids entry; purely an optimization — a lost cursor just means
        # one full-width reply
        self.poll_cursors: Dict[int, int] = {}
        self.evicted_at: Optional[float] = None
        self.drain_deadline: Optional[float] = None
        # set once the drain completed: the next heartbeat reply tells the
        # agent, which fires its on_drained callback and stops renewing
        self.drained = False

    def view(self) -> Dict[str, Any]:
        return {
            "replica_id": self.replica_id,
            "endpoint": list(self.endpoint),
            "state": self.state,
            "outstanding": len(self.outstanding),
            "assigned_total": self.assigned_total,
            "failovers": self.failovers,
            "late_results_dropped": self.late_results_dropped,
            "load": dict(self.load),
        }


def _score(rep: Replica) -> tuple:
    """Least-loaded ordering key, computed ONLY from piggybacked state and
    the router's own assignment bookkeeping — no RPC per decision. Occupancy
    (what the router has in flight there + what the replica reports queued
    and decoding) normalized by slot width, then the replica's own queue-wait
    estimate, then engine-restart count (a flapping replica loses ties), then
    registration order for determinism."""
    load = rep.load
    slots = max(1, int(load.get("max_slots", 1) or 1))
    occupancy = (
        len(rep.outstanding)
        + int(load.get("queue_depth", 0) or 0)
        + int(load.get("active_slots", 0) or 0)
    )
    return (
        occupancy / slots,
        float(load.get("estimated_queue_wait_s", 0.0) or 0.0),
        int(load.get("engine_restarts", 0) or 0),
        rep.index,
    )


class FleetView:
    """Replica membership + load bookkeeping for the router.

    The serving-tenant `_Membership` idiom applied to replicas: register
    mints a lease, heartbeats renew it, silence past `lease_s` is eviction.
    No RPCs happen here — every datum arrived piggybacked on a replica
    heartbeat or on the router's own dispatch path."""

    def __init__(self, lease_s: float = 5.0):
        self.lease_s = float(lease_s)
        self._lock = threading.Lock()
        self._replicas: Dict[str, Replica] = {}
        self._prefix = uuid.uuid4().hex[:6]
        self._next = 0
        self.evicted_total = 0

    def register(self, endpoint: Tuple[str, int]) -> Replica:
        with self._lock:
            rep = Replica(
                f"rep-{self._prefix}-{self._next}", endpoint, self._next
            )
            self._next += 1
            self._replicas[rep.replica_id] = rep
            return rep

    def heartbeat(self, replica_id: Optional[str],
                  load: Optional[Dict[str, Any]]) -> Optional[Replica]:
        """Renew a lease + absorb the piggybacked load snapshot. Returns the
        replica, or None for an id this fleet does not hold a live lease for
        (evicted/unknown — the caller's reply tells the agent to
        re-register; adopt-on-sight would resurrect a replica the router
        already failed over, aliasing late results with live ones)."""
        if not replica_id:
            return None
        with self._lock:
            rep = self._replicas.get(replica_id)
            if rep is None or rep.state not in (
                ReplicaState.LIVE, ReplicaState.DRAINING
            ):
                return rep  # caller inspects state (drained vs unknown)
            rep.last_seen = time.monotonic()
            if load:
                rep.load = {k: load[k] for k in LOAD_KEYS if k in load}
            return rep

    def get(self, replica_id: str) -> Optional[Replica]:
        with self._lock:
            return self._replicas.get(replica_id)

    def replicas(self) -> List[Replica]:
        with self._lock:
            return list(self._replicas.values())

    def live(self) -> List[Replica]:
        with self._lock:
            return [
                r for r in self._replicas.values()
                if r.state == ReplicaState.LIVE
            ]

    def expired(self, now: Optional[float] = None) -> List[Replica]:
        now = time.monotonic() if now is None else now
        with self._lock:
            return [
                r for r in self._replicas.values()
                if r.state in (ReplicaState.LIVE, ReplicaState.DRAINING)
                and now - r.last_seen > self.lease_s
            ]

    # occupancy slack the affinity preference may cost: the affine replica
    # wins while its occupancy-per-slot is within this much of the
    # least-loaded choice, so warm-prefix placement never piles a hot
    # prompt onto an already-saturated replica
    AFFINITY_SLACK = 0.25

    def choose(self, exclude: Set[str] = frozenset(),
               prefer: Optional[str] = None) -> Optional[Replica]:
        """The least-loaded LIVE replica (None when none) — pure piggybacked
        state, deterministic tie-breaks; see _score.

        Prefix affinity (ISSUE 20 / ROADMAP 2a): with `prefer` naming a
        replica, that replica wins while it is LIVE, not excluded, and its
        occupancy is within AFFINITY_SLACK of the least-loaded candidate —
        multi-turn traffic sharing a prompt head lands on the replica whose
        prefix cache is already warm. A dead/evicted/overloaded preferred
        replica degrades to the plain least-loaded choice (failover keeps
        working because the preference is a hint, never a constraint)."""
        with self._lock:
            candidates = [
                r for r in self._replicas.values()
                if r.state == ReplicaState.LIVE
                and r.replica_id not in exclude
            ]
        if not candidates:
            return None
        best = min(candidates, key=_score)
        if prefer is not None and prefer != best.replica_id:
            for r in candidates:
                if (r.replica_id == prefer
                        and _score(r)[0] <= _score(best)[0]
                        + self.AFFINITY_SLACK):
                    return r
        return best


class ReplicaAgent:
    """Replica-side fleet joiner: registers this ServingServer with the
    router and renews the lease with load-snapshot heartbeats.

    Self-fencing (the wedge story): each tick reads the session's progress
    marker; an engine that HAS work but has made no progress for longer than
    `stall_fence_s` while sitting between steps stops heartbeating — a
    wedged replica must not claim liveness, so the router's lease expiry
    fails its requests over to a survivor. When the wedge clears (the PR-10
    supervisor recovered it, or the stall simply passed) heartbeats resume;
    an evicted-then-healed replica is told to RE-REGISTER and rejoins under
    a fresh lease, while its old pump connection lets any late results it
    still produces reach the router's dedup map (dropped + counted).

    Router HA (ISSUE 18): `router_endpoints` may list a primary AND a warm
    standby. The agent manages rotation ITSELF (one single-endpoint client
    at a time, not MasterClient's internal list rotation) so that every
    control hint in a reply — `reregister`, `drain` — is provably from the
    endpoint the agent just spoke to and is honored against THAT endpoint;
    the old arrangement could race a reregister hint into a registration
    against the dead primary. Replies carry the router's per-incarnation
    `instance` token; a hint from a FOREIGN incarnation is obeyed only when
    this agent's registered incarnation is provably gone (its endpoint
    re-bound by the new incarnation, or unreachable past ROTATE_AFTER
    consecutive failures) — otherwise it is a stale reply from a
    partitioned old primary, counted and dropped (instance-token fencing,
    the double-takeover guard)."""

    # consecutive connection failures against the REGISTERED endpoint
    # before the agent concludes its router is gone and rotates
    ROTATE_AFTER = 2

    def __init__(
        self,
        router_endpoints: EndpointsLike,
        session,
        advertise: Tuple[str, int],
        client_kw: Optional[dict] = None,
        stall_fence_s: float = 5.0,
        on_drained: Optional[Callable[[], None]] = None,
    ):
        self._eps = parse_endpoints(router_endpoints)
        self._cur = 0
        self._client_kw = dict(client_kw or {"timeout": 5.0, "retries": 3})
        self._client = MasterClient(self._eps[self._cur], **self._client_kw)
        # which router incarnation + endpoint index holds our registration
        self.router_instance: Optional[str] = None
        self._reg_ep: Optional[int] = None
        self._conn_failures = 0
        self.rotations = 0
        self.stale_replies = 0
        self.session = session
        self.advertise = (str(advertise[0]), int(advertise[1]))
        self.stall_fence_s = float(stall_fence_s)
        self.on_drained = on_drained
        self.replica_id: Optional[str] = None
        self.lease_s = 5.0
        self.fenced_heartbeats = 0
        self._last_marker: Optional[tuple] = None
        self._last_change = time.monotonic()
        self._evt = threading.Event()
        self._thread = threading.Thread(
            target=self._loop, name="replica-agent", daemon=True
        )

    # -- health -------------------------------------------------------------
    def _healthy(self, now: float) -> bool:
        """False only for a genuine wedge: work pending, the engine parked
        BETWEEN steps (an in-flight step may be a multi-second first
        compile), and no progress past the fence window."""
        s = self.session
        if s is None:
            return True
        marker = s.progress_marker()
        if marker != self._last_marker:
            self._last_marker = marker
            self._last_change = now
            return True
        if not s.scheduler.has_work() or s._engine_in_step:
            self._last_change = now
            return True
        return (now - self._last_change) <= self.stall_fence_s

    def _load_snapshot(self) -> Dict[str, Any]:
        if self.session is None:
            return {}
        st = self.session.stats()
        return {k: st[k] for k in LOAD_KEYS if k in st}

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> "ReplicaAgent":
        self._register()
        self._thread.start()
        return self

    def _rotate(self) -> None:
        """Move to the next router endpoint (no-op for a single-endpoint
        list): close the current single-endpoint client and open the next."""
        if len(self._eps) <= 1:
            return
        self._client.close()
        self._cur = (self._cur + 1) % len(self._eps)
        self._client = MasterClient(self._eps[self._cur], **self._client_kw)
        self.rotations += 1
        stats.FT_EVENTS.incr("replica_router_rotate")
        log.warning("replica agent rotating to router endpoint %s:%d",
                    *self._eps[self._cur])

    def _note_conn_failure(self) -> None:
        self._conn_failures += 1
        # unregistered, any live router will do — rotate on the first
        # failure; registered, stay pinned to our router until its death is
        # confirmed (ROTATE_AFTER strikes), so one transient hiccup cannot
        # hand control hints to a different incarnation
        threshold = 1 if self.replica_id is None else self.ROTATE_AFTER
        if self._conn_failures >= threshold:
            self._rotate()

    def _register(self) -> bool:
        try:
            resp = self._client.call(
                "replica_register",
                endpoint=list(self.advertise),
                load=self._load_snapshot(),
            )
        except ConnectionError as e:
            # the router being down must not kill the replica: it keeps
            # serving direct traffic and the heartbeat loop keeps trying
            log.warning("replica register with router failed (%s); retrying "
                        "from the heartbeat loop", e)
            self._note_conn_failure()
            return False
        if "replica_id" not in resp:
            log.warning("router refused replica registration: %r", resp)
            return False
        self.replica_id = resp["replica_id"]
        self.lease_s = float(resp.get("lease_s", 5.0))
        self.router_instance = resp.get("instance")
        self._reg_ep = self._cur
        self._conn_failures = 0
        stats.FT_EVENTS.incr("replica_registered")
        return True

    def _handle_reply(self, resp: dict) -> Optional[str]:
        """Fold one heartbeat reply into agent state. Returns 'drained' when
        the agent should stop renewing, else None. Split out of the loop so
        the fencing decisions are drivable by tests without sockets."""
        inst = resp.get("instance")
        foreign = (
            inst is not None and self.router_instance is not None
            and inst != self.router_instance
        )
        if foreign:
            at_home = self._reg_ep is not None and self._cur == self._reg_ep
            lost_home = self._conn_failures >= self.ROTATE_AFTER
            if not (at_home or lost_home):
                # instance-token fencing (the double-takeover guard): a
                # DIFFERENT router incarnation answered while our own was
                # last known reachable — a stale/partitioned old primary.
                # Ignore its hints and go home; only our incarnation's
                # death (port re-bound, or unreachable past the threshold)
                # makes a foreign hint actionable.
                self.stale_replies += 1
                stats.FT_EVENTS.incr("replica_stale_router_reply")
                if self._reg_ep is not None and self._cur != self._reg_ep:
                    self._client.close()
                    self._cur = self._reg_ep
                    self._client = MasterClient(
                        self._eps[self._cur], **self._client_kw
                    )
                return None
            # our incarnation is gone: whatever this reply says, a fresh
            # registration against the endpoint that ANSWERED is the move
            self.replica_id = None
            stats.FT_EVENTS.incr("replica_reregister")
            self._register()
            return None
        self._conn_failures = 0
        if resp.get("drained"):
            # planned drain completed router-side: deregistered; tell
            # the operator hook and stop renewing
            if self.on_drained is not None:
                try:
                    self.on_drained()
                except Exception:
                    log.exception("on_drained callback failed")
            return "drained"
        if resp.get("reregister"):
            # the router evicted this lease (we were wedged/partitioned
            # past it) and we outlived the verdict: rejoin fresh — the
            # old id stays dead so late results stay distinguishable.
            # The registration goes through self._client, i.e. against
            # the endpoint that ISSUED this hint — a concurrent failover
            # can no longer race it onto a dead primary.
            self.replica_id = None
            stats.FT_EVENTS.incr("replica_reregister")
            self._register()
        return None

    def _loop(self) -> None:
        while True:
            period = max(0.05, self.lease_s / 3.0)
            if self._evt.wait(period):
                return
            now = time.monotonic()
            if not self._healthy(now):
                # self-fence: a wedged engine must not renew the lease —
                # the router's failover story depends on eviction being
                # reachable while the agent thread itself is perfectly alive
                self.fenced_heartbeats += 1
                stats.FT_EVENTS.incr("replica_heartbeat_fenced")
                continue
            if self.replica_id is None:
                self._register()
                continue
            try:
                resp = self._client.call(
                    "replica_heartbeat",
                    replica_id=self.replica_id,
                    load=self._load_snapshot(),
                )
            except ConnectionError:
                stats.FT_EVENTS.incr("replica_heartbeat_lost")
                self._note_conn_failure()
                continue
            if self._handle_reply(resp) == "drained":
                return

    def stop(self) -> None:
        """Clean leave: deregister so the router drops the lease now."""
        self._evt.set()
        self._thread.join(timeout=5.0)
        if self.replica_id is not None:
            try:
                self._client.call(
                    "replica_deregister", replica_id=self.replica_id
                )
            except ConnectionError:
                pass  # lease will simply expire
        self._client.close()

    def kill(self) -> None:
        """Crash semantics (drills): stop heartbeating WITHOUT deregistering
        — the router must discover the death through lease expiry / dead
        connections, exactly like a real process kill."""
        self._evt.set()
        self._client.close()
