"""Scale-out policy serving: N server loops behind a client-side router
(ISSUE 17 tentpole; ROADMAP item 2a–c).

The state cache was ALREADY sharded by client hash into independent
shard groups (serve/state_cache.py) — this module puts those groups
behind N micro-batching server loops:

  * ``ShardMap``        — the versioned shard→server assignment every
    router and server shares. Contiguous slices (``contiguous_partition``)
    so a re-slice moves the fewest groups.
  * ``RoutingChannel``  — the client side: one sub-channel per server
    slot, requests routed by ``client_id % total_shards → server``; a
    request NEVER crosses servers, so the PR-12 parity contract (served
    ≡ local at equal seeds/ε) holds per server. STATUS_MISROUTED replies
    carry the current map — the channel re-aims and resends once before
    surfacing a miss to the retry ladder.
  * ``ServerFleet``     — the server side: max_servers in-proc endpoints
    created UP-FRONT (addresses are static; growth is a map change, not
    address discovery), PolicyServer loops over per-server cache slices,
    PR-14 membership leases for the slot board, ``grow_server`` /
    ``shrink_server`` re-slicing with lease-handoff of whole shard
    groups (state + op-dedup bookkeeping move together, so a mid-kill
    re-route stays bit-identical), and a bouncer draining parked
    endpoints with MISROUTED+map so stale routers self-heal. ``supervise``
    adopts a dead server's orphaned shards onto the survivors — the
    kill-one-of-N chaos drill's recovery path.

Admission control (the ``serve.queue_depth_bound`` brownout) lives in
the server loop itself (serve/server.py ``_shed_overflow``); this module
only routes its STATUS_RETRY verdicts back to the ladder.
"""

import logging
import queue
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from r2d2_tpu.serve.server import PolicyServer, ServingStats
from r2d2_tpu.serve.state_cache import StateCache
from r2d2_tpu.serve.transport import (InprocEndpoint, Reply, Request,
                                      STATUS_MISROUTED)


def contiguous_partition(total_shards: int,
                         servers: Sequence[int]) -> Dict[int, List[int]]:
    """Assign ``total_shards`` global shard-group ids to the given server
    slots as contiguous slices (np.array_split semantics: sizes differ by
    at most one, earlier servers take the remainder). Contiguity is the
    re-slice-cost property: growing N→N+1 moves only boundary groups."""
    servers = list(servers)
    if not servers:
        raise ValueError("no servers to partition shards over")
    if total_shards < len(servers):
        raise ValueError(
            f"{total_shards} shard groups cannot cover {len(servers)} "
            "servers (every server needs >= 1)")
    pieces = np.array_split(np.arange(total_shards), len(servers))
    return {slot: [int(g) for g in piece]
            for slot, piece in zip(servers, pieces)}


class ShardMap:
    """Versioned shard→server assignment, shared by every router and
    server in one process and shipped over the wire as
    ``(version, assign_tuple)`` (the STATUS_MISROUTED payload). Updates
    only ever move FORWARD (apply_wire ignores stale versions), so a
    late bounce from a pre-re-slice server cannot roll a router back."""

    def __init__(self, total_shards: int,
                 assign: Optional[Sequence[int]] = None):
        self.total_shards = total_shards
        self._lock = threading.Lock()
        self._assign = tuple(int(s) for s in (
            assign if assign is not None else [0] * total_shards))
        if len(self._assign) != total_shards:
            raise ValueError(
                f"assignment covers {len(self._assign)} shards, expected "
                f"{total_shards}")
        self.version = 1

    def server_for(self, client_id: int) -> int:
        return self._assign[int(client_id) % self.total_shards]

    def shard_server(self, shard: int) -> int:
        return self._assign[int(shard)]

    def assignment(self) -> Tuple[int, ...]:
        return self._assign

    def servers(self) -> List[int]:
        """Distinct server slots in the current assignment."""
        return sorted(set(self._assign))

    def shards_of(self, slot: int) -> List[int]:
        return [g for g, s in enumerate(self._assign) if s == int(slot)]

    def update(self, assign: Sequence[int]) -> int:
        with self._lock:
            assign = tuple(int(s) for s in assign)
            if len(assign) != self.total_shards:
                raise ValueError(
                    f"assignment covers {len(assign)} shards, expected "
                    f"{self.total_shards}")
            self._assign = assign
            self.version += 1
            return self.version

    def to_wire(self) -> tuple:
        with self._lock:
            return (self.version, self._assign)

    def apply_wire(self, wire: Optional[tuple]) -> bool:
        """Adopt a wire map if it is NEWER than ours; returns whether
        anything changed (stale and None wires are ignored)."""
        if not wire:
            return False
        version, assign = int(wire[0]), tuple(int(s) for s in wire[1])
        with self._lock:
            if version <= self.version or len(assign) != self.total_shards:
                return False
            self._assign = assign
            self.version = version
            return True


class RoutingChannel:
    """Client-side router over per-server sub-channels. Implements the
    channel API the remote policies consume (``request_many`` /
    ``request`` / ``reconnect`` / ``disconnect`` / ``close``) so
    ``RemotePolicy``/``RemoteBatchedPolicy`` route transparently.

    In-proc sub-channels are driven TWO-PHASE: every lane submits before
    any reply is collected, so N server loops fill their micro-batches
    concurrently instead of serializing behind the first server's
    dispatch. Socket sub-channels use their fused ``request_many``
    (replies buffer in the kernel while later servers are drained).

    A STATUS_MISROUTED reply applies the carried map and re-sends that
    request ONCE within the call; anything still unresolved surfaces as
    a missing reply and rides the caller's retry ladder."""

    def __init__(self, channels: Dict[int, object], shard_map: ShardMap):
        self._channels = dict(channels)
        self.shard_map = shard_map
        self.reroutes = 0           # misroute bounces absorbed (tests)
        self._mirror = None         # shadow-scoring tap (ISSUE 20)

    def set_mirror(self, mirror) -> None:
        """Install a shadow tap: ``mirror(reqs, replies)`` is called with
        every request batch AND the live replies dict after each
        ``request_many`` — the ShadowScorer's intake. The tap must treat
        both as read-only; it enqueues copies and returns immediately
        (never blocks the live path). ``None`` uninstalls."""
        self._mirror = mirror

    def _route(self, reqs: Sequence[Request]) -> Dict[int, List[Request]]:
        by_server: Dict[int, List[Request]] = {}
        for r in reqs:
            slot = self.shard_map.server_for(r.client_id)
            by_server.setdefault(slot, []).append(r)
        return by_server

    def _exchange_round(self, by_server: Dict[int, List[Request]],
                        deadline: float) -> Dict[int, Reply]:
        out: Dict[int, Reply] = {}
        inproc: List[Tuple[object, List[Request], list]] = []
        socketed: List[Tuple[object, List[Request]]] = []
        for slot, reqs in by_server.items():
            ch = self._channels.get(slot)
            if ch is None:
                continue            # stale map names an unknown slot
            if hasattr(ch, "submit"):
                inproc.append((ch, reqs, [ch.submit(r) for r in reqs]))
            else:
                socketed.append((ch, reqs))
        for ch, reqs in socketed:
            remaining = max(deadline - time.monotonic(), 0.001)
            out.update(ch.request_many(reqs, timeout=remaining))
        for ch, reqs, boxes in inproc:
            for r, box in zip(reqs, boxes):
                remaining = deadline - time.monotonic()
                if remaining <= 0 or not box.event.wait(remaining):
                    continue        # missing: the caller's ladder retries
                out[r.req_id] = box.reply
        return out

    def request_many(self, reqs: List[Request],
                     timeout: float = 5.0) -> Dict[int, Reply]:
        deadline = time.monotonic() + timeout
        out = self._exchange_round(self._route(reqs), deadline)
        bounced = [r for r in reqs
                   if out.get(r.req_id) is not None
                   and out[r.req_id].status == STATUS_MISROUTED]
        if bounced:
            changed = False
            for r in bounced:
                changed |= self.shard_map.apply_wire(out[r.req_id].shard_map)
                del out[r.req_id]
            self.reroutes += len(bounced)
            if changed:
                # one in-call re-aim on the adopted map; a second bounce
                # (map still stale) is left missing for the retry ladder
                out.update(self._exchange_round(self._route(bounced),
                                                deadline))
                for r in bounced:
                    rep = out.get(r.req_id)
                    if rep is not None and rep.status == STATUS_MISROUTED:
                        self.shard_map.apply_wire(rep.shard_map)
                        del out[r.req_id]
        if self._mirror is not None:
            # shadow scoring never perturbs the live path
            try:
                self._mirror(reqs, out)
            except Exception:
                pass
        return out

    def request(self, req: Request, timeout: float = 5.0) -> Reply:
        from r2d2_tpu.serve.transport import ServeTimeout
        got = self.request_many([req], timeout=timeout)
        reply = got.get(req.req_id)
        if reply is None:
            raise ServeTimeout("no reply within timeout")
        return reply

    def reconnect(self) -> None:
        for ch in self._channels.values():
            ch.reconnect()

    def disconnect(self, client_id: int) -> None:
        ch = self._channels.get(self.shard_map.server_for(client_id))
        if ch is not None:
            ch.disconnect(client_id)

    def close(self) -> None:
        for ch in self._channels.values():
            ch.close()


class ServerFleet:
    """N PolicyServer loops over per-server state-cache slices, with
    PR-14 membership leases as the slot board and lease-handoff re-slices
    (grow/shrink/adopt). Thread-mode owner: endpoints are in-proc; the
    socket rungs (cli/serve.py, process actors) attach one
    ``SocketServerTransport`` per endpoint and ship the address table +
    assignment as the serve spec.

    All ``max_servers`` endpoints exist from construction — a parked
    slot's endpoint keeps accepting (the bouncer drains it with
    MISROUTED + the current map), so growth never changes an address."""

    def __init__(self, cfg, net, params, *, stats: ServingStats,
                 telemetry=None, client_timed: bool = False,
                 weight_poll_factory: Optional[Callable[[int], Optional[
                     Callable]]] = None,
                 weight_version: Optional[Callable[[], int]] = None,
                 weight_version_factory: Optional[Callable[[int], Optional[
                     Callable]]] = None,
                 copy_updates: bool = True, quant_stats=None,
                 warmup: Optional[bool] = None,
                 forward_fn_factory: Optional[Callable[[int], object]] = None):
        from r2d2_tpu.fleet.membership import FleetMembership
        sv = cfg.serve
        self.cfg = cfg
        self.net = net
        self._params = params
        self.stats = stats
        self.stats.admission_enabled = True
        self.telemetry = telemetry
        self._client_timed = client_timed
        self._weight_poll_factory = weight_poll_factory
        self._weight_version = weight_version
        self._weight_version_factory = weight_version_factory
        self._copy_updates = copy_updates
        self.quant_stats = quant_stats
        self._warmup = warmup
        self._fwd_factory = forward_fn_factory
        self.total_shards = sv.state_shards
        self.per_shard_slots = sv.state_slots // sv.state_shards
        self.max_servers = sv.max_servers or sv.servers
        self.membership = FleetMembership(self.max_servers,
                                          initial_active=sv.servers)
        self.endpoints = [InprocEndpoint() for _ in range(self.max_servers)]
        active = self.membership.active_slots()
        parts = contiguous_partition(self.total_shards, active)
        assign = [0] * self.total_shards
        for slot, groups in parts.items():
            for g in groups:
                assign[g] = slot
        self.shard_map = ShardMap(self.total_shards, assign)
        self.servers: Dict[int, PolicyServer] = {}
        self.local_stats: Dict[int, ServingStats] = {}
        self.adoptions = 0          # shard groups adopted off dead servers
        self._lock = threading.RLock()
        self._stop = threading.Event()
        for slot in active:
            self._start_server(slot, parts[slot])
        self._bouncer = threading.Thread(target=self._bounce_loop,
                                         daemon=True, name="serve-bouncer")
        self._bouncer.start()

    # -- server lifecycle --

    def _build_cache(self, owned: List[int]) -> StateCache:
        sv = self.cfg.serve
        h, w, s = self.net.obs_hw
        return StateCache(self.per_shard_slots * len(owned), len(owned),
                          (h, w), s, self.net.state_half,
                          lease_timeout_s=sv.lease_timeout_s,
                          action_dim=self.net.action_dim,
                          owned_shards=owned,
                          total_shards=self.total_shards)

    def _build_server(self, slot: int, cache: StateCache) -> PolicyServer:
        lstats = self.local_stats.setdefault(slot, ServingStats())
        poll = (self._weight_poll_factory(slot)
                if self._weight_poll_factory is not None else None)
        version = (self._weight_version_factory(slot)
                   if self._weight_version_factory is not None
                   else self._weight_version)
        fwd = (self._fwd_factory(slot)
               if self._fwd_factory is not None else None)
        return PolicyServer(
            self.cfg, self.net, self._params,
            endpoint=self.endpoints[slot],
            weight_poll=poll, weight_version=version,
            copy_updates=self._copy_updates, stats=self.stats,
            telemetry=self.telemetry, client_timed=self._client_timed,
            warmup=self._warmup, quant_stats=self.quant_stats,
            cache=cache, server_id=slot, shard_map=self.shard_map,
            device_index=slot, forward_fn=fwd, local_stats=lstats)

    def _start_server(self, slot: int, owned: List[int]) -> PolicyServer:
        server = self._build_server(slot, self._build_cache(owned))
        self.servers[slot] = server
        server.start()
        return server

    # -- elastic re-slice (grow / shrink / adopt) --

    def grow_server(self) -> int:
        """Lease a parked/free slot, re-slice, and hand the boundary
        shard groups off to the new server. Returns the grown slot.

        Ordering keeps the misroute window to the handoff itself: the
        new server is BUILT (incl. warmup) while the old map still
        routes everything at the donors; only then does the map flip and
        the donors detach — a straggler that raced the flip bounces off
        the donor with the NEW map already attached."""
        with self._lock:
            lease = self.membership.lease()
            slot = lease.slot
            active = sorted(set(self.servers) | {slot})
            parts = contiguous_partition(self.total_shards, active)
            owned = parts[slot]
            cache = self._build_cache(owned)
            server = self._build_server(slot, cache)
            assign = [0] * self.total_shards
            for s, groups in parts.items():
                for g in groups:
                    assign[g] = s
            self.shard_map.update(assign)
            for g in owned:
                donor = self.servers[
                    next(s for s in self.servers
                         if g in self.servers[s].cache.owned_shards)]
                with donor.cache_lock:
                    cache.restore_shard(donor.cache.detach_shard(g))
            self.servers[slot] = server
            server.start()
            return slot

    def shrink_server(self, slot: Optional[int] = None) -> int:
        """Stop one server (highest slot by default), hand its shard
        groups off to the survivors, and park its membership slot. The
        parked endpoint keeps accepting — the bouncer answers with
        MISROUTED + the new map, so routed clients re-aim without a
        single lost op (the donor's op-dedup state moved with the
        shards)."""
        with self._lock:
            if len(self.servers) <= 1:
                raise RuntimeError("cannot shrink the last serve server")
            if slot is None:
                slot = max(self.servers)
            victim = self.servers.pop(slot)
            victim.stop()
            survivors = sorted(self.servers)
            parts = contiguous_partition(self.total_shards, survivors)
            assign = [0] * self.total_shards
            for s, groups in parts.items():
                for g in groups:
                    assign[g] = s
            self._rehome(victim.cache, assign)
            self.shard_map.update(assign)
            self.membership.park(slot, reason="shrunk")
            return slot

    def kill_server(self, slot: int) -> None:
        """Chaos: stop a server loop ABRUPTLY — no handoff, membership
        still ACTIVE, map still aimed at the corpse. Clients time out /
        queue against the dead endpoint until :meth:`supervise` adopts
        the orphaned shards."""
        self.servers[slot].stop()

    def supervise(self) -> int:
        """Detect dead-but-ACTIVE servers and adopt their shard groups
        onto the survivors (the kill-one-of-N drill's recovery): the
        in-proc cache object survives its loop thread, so adoption is a
        detach/import like a clean shrink — state, leases, and op-dedup
        intact, which is what keeps the re-routed action streams
        bit-identical. Returns the number of servers reaped."""
        with self._lock:
            dead = [s for s, srv in self.servers.items() if not srv.running]
            if not dead or len(dead) == len(self.servers):
                return 0            # total outage: nothing to adopt onto
            for slot in dead:
                victim = self.servers.pop(slot)
                survivors = sorted(self.servers)
                parts = contiguous_partition(self.total_shards, survivors)
                assign = [0] * self.total_shards
                for s, groups in parts.items():
                    for g in groups:
                        assign[g] = s
                orphaned = len(victim.cache.owned_shards)
                self._rehome(victim.cache, assign)
                self.shard_map.update(assign)
                self.membership.park(slot, reason="died")
                self.adoptions += orphaned
                logging.getLogger(__name__).warning(
                    "serve server %d died; survivors adopted its shards",
                    slot)
            return len(dead)

    def _rehome(self, donor_cache: StateCache, assign: List[int]) -> None:
        """Move every shard group the donor cache still owns to the
        server the new assignment names (detach → import, whole-package
        handoff)."""
        for g in list(donor_cache.owned_shards):
            target = self.servers[assign[g]]
            state = donor_cache.detach_shard(g)
            with target.cache_lock:
                target.cache.import_shard(state)

    # -- parked-endpoint bouncer --

    def _bounce_loop(self) -> None:
        while not self._stop.is_set():
            live = set(self.servers)
            for slot, ep in enumerate(self.endpoints):
                if slot in live:
                    continue
                wire = self.shard_map.to_wire()
                while True:
                    try:
                        req, cb = ep.inbox.get_nowait()
                    except queue.Empty:
                        break
                    self.stats.on_misrouted(1)
                    try:
                        cb(Reply(req.req_id, STATUS_MISROUTED,
                                 shard_map=wire))
                    except Exception:
                        pass
            self._stop.wait(0.02)

    # -- client + telemetry surfaces --

    def connect(self) -> RoutingChannel:
        """A router over ALL slots' endpoints (parked ones bounce with
        the map, so a post-grow route needs no new connection)."""
        return RoutingChannel(
            {slot: ep.connect() for slot, ep in enumerate(self.endpoints)},
            self.shard_map)

    def serve_spec_servers(self) -> Dict[int, object]:
        """Slot → endpoint table for transport attachment (cli/serve.py
        and the orchestrator's process-actor socket rung)."""
        return dict(enumerate(self.endpoints))

    def interval_block(self, deadline_ms: Optional[float] = None,
                       max_batch: Optional[int] = None) -> Optional[dict]:
        """The fleet's ``serving`` record block: the shared aggregate
        (identical keys to single-server mode) plus a ``servers``
        sub-block with per-server rows — inspect's per-server panel."""
        block = self.stats.interval_block(deadline_ms=deadline_ms,
                                          max_batch=max_batch)
        if block is None:
            return None
        rows = {}
        with self._lock:
            for slot in sorted(self.servers):
                lb = self.local_stats[slot].interval_block()
                if lb is None:
                    continue
                # client-timed mode leaves the request histogram to the
                # clients (aggregate only); the per-server row falls back
                # to the server-side admitted latency
                lat = (lb["latency"]
                       or lb.get("admission", {}).get("admitted_latency")
                       or {})
                rows[str(slot)] = {
                    "requests": lb["requests"],
                    "latency_p50_ms": lat.get("p50_ms"),
                    "latency_p99_ms": lat.get("p99_ms"),
                    "fill_mean": lb["batch"]["fill_mean"],
                    "shed": lb.get("admission", {}).get("shed", 0),
                    "shards": len(self.servers[slot].cache.owned_shards),
                }
            block["servers"] = {
                "count": len(self.servers),
                "map_version": self.shard_map.version,
                "membership": self.membership.snapshot(),
                "rows": rows,
            }
        return block

    @property
    def running(self) -> bool:
        return any(srv.running for srv in self.servers.values())

    def stop(self, timeout: float = 5.0) -> None:
        self._stop.set()
        with self._lock:
            for srv in self.servers.values():
                srv.stop(timeout=timeout)
        self._bouncer.join(timeout=2.0)
