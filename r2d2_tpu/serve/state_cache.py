"""Server-side per-client acting state: the SEED placement move.

``ActorPolicy``/``BatchedActorPolicy`` hold three pieces of per-episode
state on the actor host — the packed LSTM hidden, the rolling frame
stack, and the last action (actor/policy.py). The central inference
service moves exactly that state here, keyed by client id, so thin
clients ship ONE raw frame per step and the recurrent context never
crosses the wire (SEED, arXiv 1910.03552 §3: "the state is kept on the
inference server").

The cache is SHARDED: client ids hash onto ``shards`` independent slot
groups, each with its own lease table — the layout under which a future
multi-device server pins shard s's arrays to device s and the per-shard
lease churn never contends. Leases:

  * ``lease``   — resolve client → slot. A new client takes a free slot
    (connect); a known client renews (and, if it had disconnected,
    RECONNECTS to its retained state — mid-episode recovery). A full
    shard evicts the stalest releasable lease (disconnected first, then
    oldest-idle) and resets the slot.
  * ``release`` — mark disconnected; state is RETAINED until
    ``lease_timeout_s`` so a bouncing client resumes where it left off.
  * ``sweep``   — evict disconnected leases idle past the timeout.

State mutations mirror the local policies' math exactly (observe_reset
broadcast fill, observe roll — parity-tested in tests/test_serve.py), so
a served actor's blocks are indistinguishable from a local one's.
"""

import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


class MisroutedClient(Exception):
    """A request reached a server whose cache does not own the client's
    shard group (the fleet re-sliced mid-flight): the server replies
    STATUS_MISROUTED with the current shard→server map instead of
    touching state, and the routing client re-aims."""

    def __init__(self, shard: int):
        super().__init__(f"client shard {shard} not owned by this cache")
        self.shard = shard


class StateCache:
    """``owned_shards``/``total_shards`` (fleet mode): this cache holds
    only the named GLOBAL shard groups of a ``total_shards``-wide hash
    space — server k's contiguous slice. Slot indices stay local and
    contiguous (owned position p covers ``[p*per_shard, (p+1)*per_shard)``);
    only the client→shard hash spans the global space. Defaults keep the
    single-server layout byte-identical (owns every shard)."""

    def __init__(self, slots: int, shards: int, frame_hw: Tuple[int, int],
                 frame_stack: int, hidden_dim: int,   # the core's state_half
                 lease_timeout_s: float = 120.0, action_dim: int = 1,
                 owned_shards: Optional[Sequence[int]] = None,
                 total_shards: Optional[int] = None):
        if shards > 0 and slots % shards != 0:
            raise ValueError(f"state slots ({slots}) must be divisible by "
                             f"shards ({shards})")
        self.slots = slots
        self.shards = shards
        self.per_shard = slots // shards if shards else 0
        self.total_shards = shards if total_shards is None else total_shards
        self._owned = (list(range(shards)) if owned_shards is None
                       else [int(g) for g in owned_shards])
        if len(self._owned) != shards:
            raise ValueError(
                f"owned_shards has {len(self._owned)} entries for "
                f"{shards} shard groups")
        self._pos = {g: p for p, g in enumerate(self._owned)}
        self.lease_timeout_s = lease_timeout_s
        self._frame_hw = tuple(frame_hw)
        self._frame_stack = frame_stack
        self._hidden_dim = hidden_dim
        self._action_dim = action_dim
        h, w = frame_hw
        self.hidden = np.zeros((slots, 2, hidden_dim), np.float32)
        self.stacked = np.zeros((slots, h, w, frame_stack), np.float32)
        self.last_action = np.full(slots, -1, np.int32)
        # Idempotent-RPC bookkeeping: the last APPLIED logical operation
        # per slot plus its cached result. A retried op (client timed
        # out, reply lost, but the first copy WAS processed) replays the
        # cached action/Q instead of re-rolling the frame stack and
        # re-advancing the hidden — one logical step mutates state
        # exactly once no matter how many copies reach the server.
        self.op_seq = np.full(slots, -1, np.int64)
        self.reply_action = np.zeros(slots, np.int64)
        self.reply_q = np.zeros((slots, max(action_dim, 1)), np.float32)
        # lease bookkeeping: slot -> client (-1 free) + per-shard maps
        self._slot_client = np.full(slots, -1, np.int64)
        self._last_seen = np.zeros(slots, np.float64)
        self._connected = np.zeros(slots, bool)
        self._leases: List[Dict[int, int]] = [dict() for _ in range(shards)]
        self.connects = 0
        self.reconnects = 0
        self.evictions = 0

    # -- leases --

    def _shard_of(self, client_id: int) -> int:
        g = int(client_id) % self.total_shards
        p = self._pos.get(g)
        if p is None:
            raise MisroutedClient(g)
        return p

    @property
    def owned_shards(self) -> List[int]:
        return list(self._owned)

    @property
    def active_clients(self) -> int:
        return int(self._connected.sum())

    @property
    def leased_slots(self) -> int:
        return int((self._slot_client >= 0).sum())

    def lease(self, client_id: int,
              now: Optional[float] = None) -> Tuple[int, bool]:
        """Resolve ``client_id`` to its slot; returns ``(slot, fresh)``
        where ``fresh`` means the slot holds NO prior state for this
        client (new connect or post-eviction re-admit) and the caller
        must reset it before use."""
        now = time.monotonic() if now is None else now
        s = self._shard_of(client_id)
        leases = self._leases[s]
        slot = leases.get(int(client_id))
        if slot is not None:
            if not self._connected[slot]:
                self.reconnects += 1     # retained state, resumed
            self._connected[slot] = True
            self._last_seen[slot] = now
            return slot, False
        slot = self._find_slot(s, now)
        leases[int(client_id)] = slot
        self._slot_client[slot] = int(client_id)
        self._connected[slot] = True
        self._last_seen[slot] = now
        self.connects += 1
        return slot, True

    def _find_slot(self, shard: int, now: float) -> int:
        lo, hi = shard * self.per_shard, (shard + 1) * self.per_shard
        owners = self._slot_client[lo:hi]
        free = np.flatnonzero(owners < 0)
        if len(free):
            return lo + int(free[0])
        # full shard: evict the stalest releasable lease — disconnected
        # leases first (their clients already left), else the oldest-idle
        # connected one (admission beats starvation; the evictee's next
        # request re-admits it with fresh state)
        ages = self._last_seen[lo:hi]
        disc = np.flatnonzero(~self._connected[lo:hi])
        cand = disc if len(disc) else np.arange(self.per_shard)
        victim = lo + int(cand[np.argmin(ages[cand])])
        self._evict(shard, victim)
        return victim

    def _evict(self, shard: int, slot: int) -> None:
        owner = int(self._slot_client[slot])
        self._leases[shard].pop(owner, None)
        self._slot_client[slot] = -1
        self._connected[slot] = False
        self.reset_slot(slot)
        self.reset_op(slot)
        self.evictions += 1

    def release(self, client_id: int,
                now: Optional[float] = None) -> bool:
        """Client disconnect: keep the state, mark the lease releasable.
        Returns True when the client actually held a lease."""
        now = time.monotonic() if now is None else now
        s = self._shard_of(client_id)
        slot = self._leases[s].get(int(client_id))
        if slot is None:
            return False
        self._connected[slot] = False
        self._last_seen[slot] = now
        return True

    def sweep(self, now: Optional[float] = None) -> int:
        """Evict disconnected leases idle past ``lease_timeout_s``;
        returns the number evicted."""
        now = time.monotonic() if now is None else now
        evicted = 0
        leased = np.flatnonzero(self._slot_client >= 0)
        for slot in leased:
            if (not self._connected[slot]
                    and now - self._last_seen[slot] > self.lease_timeout_s):
                self._evict(slot // self.per_shard, int(slot))
                evicted += 1
        return evicted

    # -- state mutations (the local policies' exact math) --

    def reset_slot(self, slot: int, obs: Optional[np.ndarray] = None) -> None:
        """Per-episode reset (ActorPolicy.reset_state / observe_reset):
        zero hidden, ``obs`` (if given) broadcast across the stack."""
        self.hidden[slot] = 0.0
        self.last_action[slot] = -1
        if obs is None:
            self.stacked[slot] = 0.0
        else:
            self.stacked[slot] = \
                (np.asarray(obs, np.float32) / 255.0)[..., None]

    def observe(self, slot: int, obs: np.ndarray, action: int) -> None:
        """Frame-stack roll + last-action record (ActorPolicy.observe)."""
        self.stacked[slot] = np.roll(self.stacked[slot], -1, axis=-1)
        self.stacked[slot][..., -1] = np.asarray(obs, np.float32) / 255.0
        self.last_action[slot] = np.int32(action)

    # -- batch assembly --

    def gather(self, slots: List[int]
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        idx = np.asarray(slots, np.int64)
        return (self.stacked[idx], self.last_action[idx], self.hidden[idx])

    def write_hidden(self, slot: int, hidden: np.ndarray) -> None:
        self.hidden[slot] = hidden

    # -- idempotent-op bookkeeping --

    def reset_op(self, slot: int) -> None:
        """Forget the slot's op history (fresh lease / eviction) — a new
        client's op numbering starts over."""
        self.op_seq[slot] = -1

    def record_op(self, slot: int, op_seq: int, action: int,
                  q: np.ndarray) -> None:
        self.op_seq[slot] = op_seq
        self.reply_action[slot] = action
        self.reply_q[slot] = q

    def cached_reply(self, slot: int) -> Tuple[int, np.ndarray]:
        return int(self.reply_action[slot]), self.reply_q[slot].copy()

    # -- shard lease-handoff (the elastic serve fleet's re-slice) --
    #
    # A shard group moves between servers as ONE package: its state
    # arrays (hidden/stack/last_action), the idempotent-op bookkeeping
    # (op_seq + cached replies — a retried op deduplicates across the
    # handoff, which is what makes a mid-kill re-route bit-identical),
    # and the lease table with connect/last-seen ages (disconnect
    # retention survives the move).

    _ARRAYS = ("hidden", "stacked", "last_action", "op_seq",
               "reply_action", "reply_q", "_slot_client", "_last_seen",
               "_connected")

    def export_shard(self, shard: int) -> dict:
        """Copy global shard group ``shard``'s full state out (the donor
        keeps it — see :meth:`detach_shard` for the removing variant)."""
        p = self._pos[int(shard)]
        lo, hi = p * self.per_shard, (p + 1) * self.per_shard
        state = {name: getattr(self, name)[lo:hi].copy()
                 for name in self._ARRAYS}
        state["shard"] = int(shard)
        state["per_shard"] = self.per_shard
        state["leases"] = {c: s - lo for c, s in self._leases[p].items()}
        return state

    def detach_shard(self, shard: int) -> dict:
        """Export global shard group ``shard`` and REMOVE it from this
        cache — the donor half of a re-slice. Later requests hashing onto
        it raise :class:`MisroutedClient` (→ STATUS_MISROUTED + map)."""
        state = self.export_shard(shard)
        p = self._pos.pop(int(shard))
        lo = p * self.per_shard
        keep = np.ones(self.slots, bool)
        keep[lo:lo + self.per_shard] = False
        for name in self._ARRAYS:
            setattr(self, name, getattr(self, name)[keep])
        self._leases.pop(p)
        # the compaction shifted every later group's rows down one
        # group: rebase those groups' lease slot indices to match
        for q in range(p, len(self._leases)):
            self._leases[q] = {c: s - self.per_shard
                               for c, s in self._leases[q].items()}
        self._owned.pop(p)
        self._pos = {g: q for q, g in enumerate(self._owned)}
        self.shards -= 1
        self.slots -= self.per_shard
        return state

    def import_shard(self, state: dict) -> None:
        """Append a handed-off shard group (the adopter half). The group
        arrives with its leases, ages, and op bookkeeping intact, so
        retained-state reconnects and retry dedup span the handoff."""
        if state["per_shard"] != self.per_shard:
            raise ValueError(
                f"shard geometry mismatch: incoming per_shard "
                f"{state['per_shard']} != {self.per_shard}")
        g = int(state["shard"])
        if g in self._pos:
            raise ValueError(f"shard {g} already owned")
        lo = self.slots
        for name in self._ARRAYS:
            setattr(self, name,
                    np.concatenate([getattr(self, name), state[name]]))
        self._leases.append({c: s + lo for c, s in state["leases"].items()})
        self._owned.append(g)
        self._pos[g] = len(self._owned) - 1
        self.shards += 1
        self.slots += self.per_shard

    def restore_shard(self, state: dict) -> None:
        """Overwrite an ALREADY-OWNED (fresh) shard group in place with
        handed-off state — how a newly-grown server adopts the shards the
        re-slice assigned to it."""
        g = int(state["shard"])
        if state["per_shard"] != self.per_shard:
            raise ValueError(
                f"shard geometry mismatch: incoming per_shard "
                f"{state['per_shard']} != {self.per_shard}")
        p = self._pos[g]
        lo = p * self.per_shard
        for name in self._ARRAYS:
            getattr(self, name)[lo:lo + self.per_shard] = state[name]
        self._leases[p] = {c: s + lo for c, s in state["leases"].items()}
