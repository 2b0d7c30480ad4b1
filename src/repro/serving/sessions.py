"""Recurrent session cache: per-client carry state kept resident between
requests so a streaming step is O(1) instead of O(window).

``SessionCache`` is model-agnostic (it stores opaque carries with byte
accounting); ``RecurrentSessionRunner`` binds it to a forecaster with
the device-resident lane lifecycle (``init_slots`` / ``prefill`` /
``insert`` / ``generate`` / ``extract``, the ``StreamingForecaster``
protocol). The runner IS a lane allocator: sessions live in fixed
device lanes between steps, and a batched ``step_many`` is "ensure
resident → one fused generate dispatch → read only the requested rows"
— the cache is the *spill tier* that holds carries only for sessions
LRU-evicted off the lanes (or spilled for migration), bitwise-identical
on reload. Eviction is LRU with an optional TTL and byte budget. A
miss replays the client's window prefix through the same compiled step
the lanes run, so — provided the client supplies its history on a miss
— eviction never changes the numbers a client sees, only the latency.
Misses without history start a fresh session from zero state (or
raise, with ``on_miss="error"``).

``ShardedSessionCache`` splits the fleet budget over per-shard
``SessionCache`` instances keyed by a consistent hash of the client id
(the same rendezvous hash the request router uses, so a client's carry
lives on the shard its requests land on). LRU/TTL state and locks are
shard-local: session traffic on one shard never contends with another.
Membership is LIVE: ``add_shard``/``remove_shard`` follow the router's
assignment laws — only the clients the rendezvous hash moves (to an
arriving shard, or off a departing one) are migrated, carries intact,
and the fleet budget is re-split over the new shard set.

Carries are OPAQUE throughout: the LSTM stores per-layer (h, c)
tuples, a zoo hybrid its decode cache (SSM, conv and key/value state of
about 110 MB at published widths, spilled to host memory); an
``EnsembleForecaster`` session stores one composite
``{member_key: member_carry}`` dict under ONE client id. The runner
never looks inside — init/step/replay/extract on the ensemble build and
split the dict — so a composite session spills, migrates and re-homes
as a unit, and version mismatches (the ensemble version string changes
when ANY member is swapped) re-prime every member from history in one
replay, exactly like a single model.
"""

from __future__ import annotations

import dataclasses
import threading
import time
import warnings
from collections import OrderedDict
from typing import Any

from repro.obs import trace as obs_trace
from repro.serving.forecaster import (_donate_default, input_spec,
                                      streams)
from repro.serving.router import ConsistentRouter
from repro.serving.telemetry import Telemetry


@dataclasses.dataclass
class _Session:
    carry: Any
    nbytes: int
    last_used: float
    created: float
    steps: int = 0
    version: int = 0             # model version the carry was built under


class SessionCache:
    """LRU + TTL cache of per-client carries with capacity accounting."""

    def __init__(self, max_sessions: int = 4096,
                 max_bytes: int | None = None,
                 ttl_s: float | None = None,
                 telemetry: Telemetry | None = None,
                 clock=time.monotonic):
        if max_sessions < 1:
            raise ValueError("max_sessions must be >= 1")
        self.max_sessions = max_sessions
        self.max_bytes = max_bytes
        self.ttl_s = ttl_s
        self.telemetry = telemetry
        self._clock = clock
        self._lock = threading.Lock()
        self._sessions: OrderedDict[str, _Session] = OrderedDict()
        self.nbytes_in_use = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.oversize_admissions = 0   # carries bigger than max_bytes
        self._warned_oversize = False

    def __len__(self) -> int:
        with self._lock:
            return len(self._sessions)

    def __contains__(self, client_id: str) -> bool:
        with self._lock:
            return client_id in self._sessions

    def get(self, client_id: str):
        """Return the cached carry (refreshing LRU order) or None."""
        entry = self.get_entry(client_id)
        return entry[0] if entry is not None else None

    def get_entry(self, client_id: str) -> tuple[Any, int] | None:
        """Like ``get`` but returns (carry, model_version) so callers can
        detect carries built under a weight version that has since been
        hot-swapped out."""
        with self._lock:
            expired = self._expire_locked()
            s = self._sessions.get(client_id)
            hit = s is not None
            if hit:
                self._sessions.move_to_end(client_id)
                s.last_used = self._clock()
                self.hits += 1
            else:
                self.misses += 1
        if self.telemetry is not None:
            if expired:
                self.telemetry.record_eviction(expired)
            self.telemetry.record_cache(hit)
        return (s.carry, s.version) if hit else None

    def put(self, client_id: str, carry, nbytes: int,
            version: int = 0) -> None:
        evicted = 0
        warn_oversize = False
        with self._lock:
            now = self._clock()
            old = self._sessions.pop(client_id, None)
            if old is not None:
                self.nbytes_in_use -= old.nbytes
            if self.max_bytes is not None and nbytes > self.max_bytes:
                # a single carry bigger than the whole byte budget: it is
                # admitted (evicting it would silently restart the
                # client's stream from zero state) but the cache sits
                # over budget until normal LRU pressure reclaims it —
                # warn once and surface ``over_budget`` in stats()
                # instead of doing that silently
                self.oversize_admissions += 1
                if not self._warned_oversize:
                    self._warned_oversize = True
                    warn_oversize = True
            s = _Session(carry=carry, nbytes=nbytes, last_used=now,
                         created=old.created if old else now,
                         steps=(old.steps + 1) if old else 1,
                         version=version)
            self._sessions[client_id] = s
            self.nbytes_in_use += nbytes
            evicted = self._evict_over_locked()
        if warn_oversize:
            warnings.warn(
                f"session carry for {client_id!r} is {nbytes} bytes, over "
                f"the cache's max_bytes={self.max_bytes}: admitted, but "
                f"the cache is over budget until it is evicted "
                f"(stats()['over_budget'] tracks this)",
                RuntimeWarning, stacklevel=2)
        if evicted and self.telemetry is not None:
            self.telemetry.record_eviction(evicted)

    def put_new(self, client_id: str, carry, nbytes: int,
                version: int = 0) -> bool:
        """Insert only if absent, atomically — the migration path. A
        carry arriving from a departing shard must never clobber a
        fresher one a concurrent step already wrote to the new owner.
        Returns whether the carry was installed."""
        with self._lock:
            if client_id in self._sessions:
                return False
            now = self._clock()
            self._sessions[client_id] = _Session(
                carry=carry, nbytes=nbytes, last_used=now, created=now,
                steps=1, version=version)
            self.nbytes_in_use += nbytes
            evicted = self._evict_over_locked()
        if evicted and self.telemetry is not None:
            self.telemetry.record_eviction(evicted)
        return True

    def _evict_over_locked(self) -> int:
        """Evict LRU entries until within the session/byte budgets (a
        lone over-budget session is kept — see ``put``)."""
        evicted = 0
        while len(self._sessions) > self.max_sessions or (
                self.max_bytes is not None
                and self.nbytes_in_use > self.max_bytes
                and len(self._sessions) > 1):
            _, victim = self._sessions.popitem(last=False)
            self.nbytes_in_use -= victim.nbytes
            self.evictions += 1
            evicted += 1
        return evicted

    _KEEP = object()               # resize sentinel: leave a budget as-is

    def resize(self, max_sessions=None, max_bytes=_KEEP) -> int:
        """Change the budgets (fleet re-split on membership change),
        evicting LRU entries down to the new limits. ``max_bytes=None``
        removes the byte budget; omit it to keep the current one.
        Returns #evicted."""
        with self._lock:
            if max_sessions is not None:
                if max_sessions < 1:
                    raise ValueError("max_sessions must be >= 1")
                self.max_sessions = max_sessions
            if max_bytes is not SessionCache._KEEP:
                self.max_bytes = max_bytes
            evicted = self._evict_over_locked()
        if evicted and self.telemetry is not None:
            self.telemetry.record_eviction(evicted)
        return evicted

    def clients(self) -> list[str]:
        """Ids of the currently cached sessions (LRU -> MRU order)."""
        with self._lock:
            return list(self._sessions)

    def export(self, client_ids=None) -> list[tuple[str, Any, int, int]]:
        """Remove and return ``(client_id, carry, nbytes, version)``
        tuples — for ``client_ids`` (missing ids skipped), or every
        session when None. This is the migration path: a shard handing
        its clients to the new owners on membership change."""
        with self._lock:
            ids = list(self._sessions) if client_ids is None \
                else [c for c in client_ids if c in self._sessions]
            out = []
            for cid in ids:
                s = self._sessions.pop(cid)
                self.nbytes_in_use -= s.nbytes
                out.append((cid, s.carry, s.nbytes, s.version))
            return out

    def snapshot(self, client_ids=None) -> list[tuple[str, Any, int, int]]:
        """READ ``(client_id, carry, nbytes, version)`` tuples without
        removing them — the durable-checkpoint path (``export`` is the
        migration path and drains what it returns). No LRU refresh and
        no hit/miss accounting: observing the cache for a checkpoint
        must not perturb its eviction order or its telemetry."""
        with self._lock:
            ids = list(self._sessions) if client_ids is None \
                else [c for c in client_ids if c in self._sessions]
            return [(cid, self._sessions[cid].carry,
                     self._sessions[cid].nbytes,
                     self._sessions[cid].version) for cid in ids]

    def peek_version(self, client_id: str) -> int | None:
        """The version stamp of a cached session, without touching LRU
        order or hit/miss counts (None when absent) — the partition
        re-adoption reconcile compares these against the store."""
        with self._lock:
            s = self._sessions.get(client_id)
            return s.version if s is not None else None

    def drop(self, client_id: str) -> bool:
        with self._lock:
            s = self._sessions.pop(client_id, None)
            if s is not None:
                self.nbytes_in_use -= s.nbytes
            return s is not None

    def _expire_locked(self) -> int:
        if self.ttl_s is None:
            return 0
        cutoff = self._clock() - self.ttl_s
        stale = [cid for cid, s in self._sessions.items()
                 if s.last_used < cutoff]
        for cid in stale:
            s = self._sessions.pop(cid)
            self.nbytes_in_use -= s.nbytes
            self.evictions += 1
        return len(stale)

    def stats(self) -> dict:
        with self._lock:
            lookups = self.hits + self.misses
            return {
                "sessions": len(self._sessions),
                "nbytes_in_use": self.nbytes_in_use,
                "hits": self.hits,
                "misses": self.misses,
                "hit_rate": self.hits / lookups if lookups else 0.0,
                "evictions": self.evictions,
                "over_budget": (self.max_bytes is not None
                                and self.nbytes_in_use > self.max_bytes),
                "oversize_admissions": self.oversize_admissions,
            }


class ShardedSessionCache:
    """Fleet session cache: the ``SessionCache`` API over per-shard
    caches, routed by a consistent hash of the client id.

    ``max_sessions`` / ``max_bytes`` are FLEET budgets, split exactly
    over shards (remainders go to the lowest shard ids, so the fleet
    total never exceeds the budget); eviction is shard-local LRU (a hot
    shard evicts its own LRU client even while another shard has room —
    the price of lock-free-across-shards operation). Pass the mesh's
    ``router`` so session shards coincide with serving shards, or omit
    it for a standalone sharded cache.

    Membership is a live view of the router: ``add_shard`` /
    ``remove_shard`` migrate exactly the clients the rendezvous hash
    moves (carries intact) and re-split the fleet budget — the
    assignment laws the router is property-tested for extend to the
    cached sessions."""

    def __init__(self, n_shards: int = 2, max_sessions: int = 4096,
                 max_bytes: int | None = None, ttl_s: float | None = None,
                 telemetry: Telemetry | None = None, clock=time.monotonic,
                 router=None):
        if router is None and n_shards < 1:
            raise ValueError("n_shards must be >= 1")
        self.router = router if router is not None \
            else ConsistentRouter(range(n_shards))
        self.telemetry = telemetry
        self.max_sessions_fleet = max_sessions
        self.max_bytes_fleet = max_bytes
        self.ttl_s = ttl_s
        self._clock = clock
        self._members_lock = threading.Lock()
        ids = self.router.shard_ids
        if max_sessions < len(ids):
            raise ValueError(
                f"max_sessions={max_sessions} must be >= n_shards="
                f"{len(ids)} (every shard needs at least one slot)")
        self.shards: dict[int, SessionCache] = {
            sid: SessionCache(
                max_sessions=self._split(max_sessions, i, len(ids)),
                max_bytes=(None if max_bytes is None
                           else self._split(max_bytes, i, len(ids))),
                ttl_s=ttl_s, telemetry=telemetry, clock=clock)
            for i, sid in enumerate(ids)}

    @staticmethod
    def _split(total: int, i: int, n: int) -> int:
        return total // n + (1 if i < total % n else 0)

    @property
    def n_shards(self) -> int:
        return len(self.shards)

    # -- live membership ---------------------------------------------------
    def _resplit_locked(self) -> None:
        n = len(self.shards)
        for i, sid in enumerate(sorted(self.shards)):
            self.shards[sid].resize(
                max_sessions=self._split(self.max_sessions_fleet, i, n),
                max_bytes=(None if self.max_bytes_fleet is None
                           else self._split(self.max_bytes_fleet, i, n)))

    def add_shard(self, shard_id: int) -> None:
        """Open a shard-local cache for ``shard_id`` (adding it to the
        router if the caller has not already) and migrate exactly the
        clients the rendezvous hash re-homes onto it, carries intact."""
        sid = int(shard_id)
        with self._members_lock:
            if sid in self.shards:
                return
            if self.max_sessions_fleet < len(self.shards) + 1:
                raise ValueError(
                    f"fleet max_sessions={self.max_sessions_fleet} cannot "
                    f"give shard {sid} a slot (already {len(self.shards)} "
                    f"shards)")
            self.shards[sid] = SessionCache(
                max_sessions=1, ttl_s=self.ttl_s, telemetry=self.telemetry,
                clock=self._clock)
            if sid not in self.router.shard_ids:
                self.router.add_shard(sid)
            self._resplit_locked()
            # minimal disruption: only clients the new shard WINS move;
            # insert-if-absent so a fresher carry a concurrent step
            # already wrote to the new shard is never clobbered
            for old_sid, cache in list(self.shards.items()):
                if old_sid == sid:
                    continue
                moving = [c for c in cache.clients()
                          if self.router.shard_for(c) == sid]
                for cid, carry, nbytes, version in cache.export(moving):
                    self.shards[sid].put_new(cid, carry, nbytes,
                                             version=version)

    def remove_shard(self, shard_id: int) -> None:
        """Close ``shard_id``'s cache (removing it from the router if the
        caller has not already) and hand its clients — and only its
        clients — to their new owner shards, carries intact.

        A ``get`` racing the migration window can still miss (the carry
        is in flight between shards); a re-homed client that keeps
        streaming through the change should supply its history on a
        miss (standard consistent-hashing cache semantics — the session
        runner replays it through the same compiled step). ``put`` is
        loss-proof: one landing in a just-removed shard's cache detects
        the change and re-routes itself."""
        sid = int(shard_id)
        with self._members_lock:
            if sid not in self.shards:
                raise KeyError(f"no session shard {sid}; have "
                               f"{sorted(self.shards)}")
            if len(self.shards) == 1:
                raise ValueError("cannot remove the last session shard")
            if sid in self.router.shard_ids:
                self.router.remove_shard(sid)
            departing = self.shards.pop(sid)
            self._resplit_locked()
            for cid, carry, nbytes, version in departing.export():
                # insert-if-absent: a concurrent step may already have
                # written a fresher carry on the new owner
                self.shards[self.router.shard_for(cid)].put_new(
                    cid, carry, nbytes, version=version)

    def shard_for(self, client_id: str) -> int:
        return self.router.shard_for(str(client_id))

    def _shard(self, client_id: str) -> SessionCache:
        sid = self.shard_for(client_id)
        cache = self.shards.get(sid)
        if cache is None:                     # router mutated directly
            raise KeyError(
                f"router maps {client_id!r} to shard {sid} but this cache "
                f"has no such shard — change membership through "
                f"add_shard/remove_shard (or the owning mesh), not by "
                f"mutating the router")
        return cache

    # -- SessionCache API, routed ------------------------------------------
    def get(self, client_id: str):
        return self._shard(client_id).get(client_id)

    def get_entry(self, client_id: str):
        return self._shard(client_id).get_entry(client_id)

    def put(self, client_id: str, carry, nbytes: int,
            version: int = 0) -> None:
        while True:
            sid = self.shard_for(client_id)
            cache = self._shard(client_id)
            cache.put(client_id, carry, nbytes, version=version)
            if self.shards.get(sid) is cache \
                    and self.shard_for(client_id) == sid:
                return
            # membership changed mid-put: the entry may sit in a cache
            # that was just removed (its export already ran) or that no
            # longer owns the client — never lose the carry silently;
            # pull it back and re-route
            cache.drop(client_id)

    def drop(self, client_id: str) -> bool:
        return self._shard(client_id).drop(client_id)

    def __len__(self) -> int:
        return sum(len(s) for s in list(self.shards.values()))

    def __contains__(self, client_id: str) -> bool:
        return client_id in self._shard(client_id)

    @property
    def hits(self) -> int:
        return sum(s.hits for s in list(self.shards.values()))

    @property
    def misses(self) -> int:
        return sum(s.misses for s in list(self.shards.values()))

    @property
    def evictions(self) -> int:
        return sum(s.evictions for s in list(self.shards.values()))

    @property
    def nbytes_in_use(self) -> int:
        return sum(s.nbytes_in_use for s in list(self.shards.values()))

    def stats(self) -> dict:
        """Fleet aggregate plus per-shard session/byte occupancy."""
        shards = dict(self.shards)       # snapshot vs live membership
        shard_stats = {sid: shards[sid].stats() for sid in sorted(shards)}
        lookups = self.hits + self.misses
        return {
            "sessions": sum(st["sessions"] for st in shard_stats.values()),
            "nbytes_in_use": sum(st["nbytes_in_use"]
                                 for st in shard_stats.values()),
            "hits": self.hits,
            "misses": self.misses,
            "hit_rate": self.hits / lookups if lookups else 0.0,
            "evictions": self.evictions,
            "over_budget": any(st["over_budget"]
                               for st in shard_stats.values()),
            "shards": len(self.shards),
            "sessions_by_shard": [st["sessions"]
                                  for st in shard_stats.values()],
        }


DEFAULT_NUM_SLOTS = 64               # lanes per runner when unspecified


class RecurrentSessionRunner:
    """Streaming serving for a recurrent forecaster: each client is a
    session whose state lives in a device-resident decode lane between
    requests.

    ``forecaster`` may be the forecaster itself or a zero-arg provider
    returning the *current* forecaster (e.g. ``lambda: registry.get(key)``)
    so a runner keeps tracking a registry key across weight hot-swaps.
    Carries are stamped with the model version they were built under; a
    step that observes a newer version re-primes the carry lazily — by
    replaying ``history`` through the new weights when given, otherwise by
    carrying the live hidden state across (valid shapes: swapped versions
    share the config) — instead of dropping the session.

    The runner is the lane ALLOCATOR over ``num_slots`` lanes (at least
    one): an LRU over lanes decides which sessions stay device-resident;
    a session that loses its lane spills its carry to the cache (the
    spill tier) and reloads bitwise-identically on its next step; lanes
    idle past the cache's TTL are expired like cache entries. Over a
    ``ShardedSessionCache`` (``mesh.session_cache()``) the sessions
    still live in lanes, which membership changes cannot see: the caller
    spills them (``spill_all()``) before adding or removing a shard, so
    the cache migrates every carry.
    """

    def __init__(self, forecaster, cache: SessionCache | None = None,
                 on_miss: str = "zeros",
                 num_slots: int = DEFAULT_NUM_SLOTS):
        if callable(forecaster) and not hasattr(forecaster, "step"):
            self._provider = forecaster
        else:
            self._provider = None
            self.forecaster = forecaster
        self.last_step_slots = 0     # lane slots of the last step_many
        fc = self._resolve()
        if on_miss not in ("zeros", "error"):
            raise ValueError("on_miss must be 'zeros' or 'error'")
        if not (hasattr(fc, "init_slots") and streams(fc)):
            raise TypeError(
                f"forecaster {type(fc).__name__} does not stream through "
                f"decode lanes (missing init_slots)")
        if num_slots < 1:
            raise TypeError(f"a session runner needs at least one decode "
                            f"lane, got num_slots={num_slots}")
        self.cache = cache if cache is not None else SessionCache()
        self.on_miss = on_miss
        self._nbytes = fc.carry_nbytes(1)
        self.reprimes = 0            # carries replayed onto new weights
        self.carried_across_swap = 0  # carries reused without history
        # -- device-resident decode slots --------------------------------
        self._slots = fc.init_slots(num_slots)
        self.num_slots = self._slots.num_slots
        self._slots_lock = threading.Lock()
        self._lanes: OrderedDict[str, int] = OrderedDict()  # cid -> lane
        self._free = list(reversed(range(self.num_slots)))  # pop() -> 0..
        self._lane_stamp: dict[str, int] = {}
        self._lane_last_used: dict[str, float] = {}
        self.slot_inserts = 0        # sessions written into a lane
        self.slot_spills = 0         # lane carries spilled to the cache
        self.slot_expiries = 0       # lanes freed by TTL (state dropped)
        # one lane's carry in bytes by kind of state, and the positions
        # the generate waves' stepped lanes held (attention reads them)
        self.lane_state_bytes = (fc.lane_state_bytes()
                                 if hasattr(fc, "lane_state_bytes")
                                 else {"carry": self._nbytes})
        self.kv_tokens_read = 0
        # generate waves whose program updated the SSM state in place
        self.ssm_inplace_waves = 0
        window = getattr(fc, "window", None)
        if window and getattr(fc, "feature_dim", 0):
            import numpy as np

            # compile the full-window replay program HERE, off the
            # serving path — otherwise the first cache miss / swap
            # re-prime pays the jit compile at serve time
            fc.replay(np.zeros((1, window, fc.feature_dim), np.float32))
        # same deal for the lane programs: the first flush must not pay
        # the generate compile at serve time
        fc.warm_slots(self.num_slots)

    def _resolve(self):
        fc = self._provider() if self._provider is not None \
            else self.forecaster
        for attr in ("init_carry", "step", "replay"):
            if not hasattr(fc, attr):
                raise TypeError(
                    f"forecaster {type(fc).__name__} does not "
                    f"support incremental serving (missing {attr!r})")
        return fc

    def _resolve_carry(self, fc, client_id: str, hist, version: int):
        """Carry-resolution shared by ``step`` and ``step_many``: cache
        hit (with lazy re-prime when the weights hot-swapped under the
        carry), else rebuild from history, else zero state / error.
        Returns (carry, version stamp for the put-back)."""
        entry = self.cache.get_entry(client_id)
        carry = None
        stamp = version
        if entry is not None:
            carry, carry_version = entry
            if carry_version != version:
                if hist is not None:
                    _, _, carry = fc.replay(hist[None])
                    self.reprimes += 1
                    if self.cache.telemetry is not None:
                        self.cache.telemetry.record_reprime()
                else:
                    # same config, new weights: the live state stays a
                    # usable prefix approximation until history arrives —
                    # keep the OLD stamp so a later step that does bring
                    # history still sees the mismatch and re-primes
                    self.carried_across_swap += 1
                    stamp = carry_version
        if carry is None:
            if hist is not None:
                _, _, carry = fc.replay(hist[None])
            elif self.on_miss == "error":
                raise KeyError(
                    f"no session for {client_id!r} and no history given")
            else:
                carry = fc.init_carry(1)
        return carry, stamp

    def _clamp_history(self, fc, history):
        if history is None:
            return None
        import numpy as np

        hist = np.asarray(history, input_spec(fc)[1])
        window = getattr(fc, "window", None)
        if window and hist.shape[0] > window:
            # clamp to the newest `window` steps: the serving
            # contract replays window prefixes (the model is causal
            # over a sliding window), and an unbounded set of
            # history lengths would compile one replay program per
            # distinct length
            hist = hist[-window:]
        return hist

    def step(self, client_id: str, x_t, history=None):
        """One streaming step for ``client_id``. ``x_t`` is one feature
        vector [F] (or [1, F]). On a miss the carry is rebuilt from
        ``history`` ([T, F] window prefix, replayed through the same
        compiled step the lanes run). Without history, a miss starts
        a fresh zero-state session — correct for a new client, but an
        evicted client's forecasts silently restart from scratch, so
        deployments where eviction is expected should pass history or
        construct the runner with ``on_miss="error"``.
        Returns (forecast, p_extreme) scalars."""
        return self.step_many([(client_id, x_t, history)])[0]

    def step_many(self, items):
        """Batched streaming step: ``items`` is a list of
        ``(client_id, x_t, history)`` tuples (history may be None). Every
        session is made lane-resident, then all of them step in ONE
        fused generate over the lane state (chunked only when a wave
        holds more distinct clients than there are lanes), bitwise-
        identical to calling ``step`` per item.

        Duplicate client ids are legal: later occurrences run in a
        follow-up wave so each step sees the carry its predecessor
        wrote, preserving per-client stream order. Returns
        ``[(forecast, p_extreme), ...]`` in item order."""
        fc = self._resolve()
        self.last_step_slots = 0
        if not items:
            return []
        version = getattr(fc, "version", 0)
        results: list = [None] * len(items)
        # waves: index items so one client's steps never share a batch
        waves: list[list[int]] = []
        seen_at: dict[str, int] = {}
        for idx, (cid, _x, _h) in enumerate(items):
            wave = seen_at.get(cid, -1) + 1
            seen_at[cid] = wave
            if wave == len(waves):
                waves.append([])
            waves[wave].append(idx)
        S = self.num_slots
        n_chunks = 0
        with obs_trace.span("repro.session_step_many"):
            with self._slots_lock:
                self._expire_lanes_locked(fc)
                for wave in waves:
                    for lo in range(0, len(wave), S):
                        n_chunks += 1
                        self._generate_chunk_locked(
                            fc, items, wave[lo:lo + S], version, results)
                tel = self.cache.telemetry
                if tel is not None:
                    tel.record_slots(active=len(self._lanes), lanes=S)
        # lane slots this call dispatched: the engine's occupancy
        # telemetry reads it
        self.last_step_slots = n_chunks * S
        return results

    # -- slot allocator ----------------------------------------------------
    def _expire_lanes_locked(self, fc) -> None:
        """TTL sweep over the lanes, mirroring the cache's expiry: a
        lane idle past the cache's TTL is freed and its state DROPPED
        (not spilled) — exactly what the cache would have done to the
        entry. The client re-primes from history on its next step."""
        ttl = self.cache.ttl_s
        if ttl is None or not self._lanes:
            return
        cutoff = self.cache._clock() - ttl
        stale = [cid for cid, _lane in self._lanes.items()
                 if self._lane_last_used.get(cid, cutoff) < cutoff]
        for cid in stale:
            lane = self._lanes.pop(cid)
            self._lane_stamp.pop(cid, None)
            self._lane_last_used.pop(cid, None)
            fc.release(self._slots, lane)
            self._free.append(lane)
            self.slot_expiries += 1
        if stale and self.cache.telemetry is not None:
            self.cache.telemetry.record_eviction(len(stale))

    def _alloc_lane_locked(self, fc) -> int:
        """A free lane, else the LRU lane — its session spills its
        carry to the cache (the spill tier) and reloads bitwise-equal
        on its next step."""
        if self._free:
            return self._free.pop()
        victim, lane = next(iter(self._lanes.items()))
        self._lanes.pop(victim)
        carry = fc.extract(self._slots, lane)
        self.cache.put(victim, carry, self._nbytes,
                       version=self._lane_stamp.pop(victim))
        self._lane_last_used.pop(victim, None)
        self.slot_spills += 1
        if self.cache.telemetry is not None:
            self.cache.telemetry.record_slots(spills=1)
        return lane

    def _ensure_resident_locked(self, fc, cid, hist, version) -> int:
        """The 'ensure resident' half of a slot step: lane hit refreshes
        LRU (re-priming in place if the weights hot-swapped under the
        lane); otherwise the carry is resolved through the spill tier /
        history / zeros path and inserted into an allocated lane."""
        now = self.cache._clock()
        lane = self._lanes.get(cid)
        if lane is not None:
            self._lanes.move_to_end(cid)
            self._lane_last_used[cid] = now
            if self._lane_stamp[cid] != version:
                if hist is not None:
                    _, _, carry = fc.prefill(hist[None])
                    self._guarded_insert(fc, lane, carry)
                    self._lane_stamp[cid] = version
                    self.reprimes += 1
                    if self.cache.telemetry is not None:
                        self.cache.telemetry.record_reprime()
                else:
                    # same config, new weights: keep the OLD stamp so a
                    # later step that does bring history still re-primes
                    self.carried_across_swap += 1
            if self.cache.telemetry is not None:
                self.cache.telemetry.record_cache(True)
            return lane
        # lane miss: spill tier -> history prefill -> zeros/error, with
        # the same version semantics as the cache path
        with obs_trace.span("repro.sessions.lane_miss"):
            carry, stamp = self._resolve_carry(fc, cid, hist, version)
            self.cache.drop(cid)          # the lane owns the state now
            lane = self._alloc_lane_locked(fc)
            self._guarded_insert(fc, lane, carry)
            self._lanes[cid] = lane
            self._lane_stamp[cid] = stamp
            self._lane_last_used[cid] = now
            self.slot_inserts += 1
            if self.cache.telemetry is not None:
                self.cache.telemetry.record_slots(inserts=1)
        return lane

    def _guarded_insert(self, fc, lane, carry) -> None:
        try:
            fc.insert(self._slots, lane, carry)
        except Exception:
            if _donate_default():
                self._reset_slots_locked(fc)
            raise

    def _reset_slots_locked(self, fc) -> None:
        """A donating program failed mid-flight: the slot state may be
        consumed. Rebuild it empty — every resident session is dropped
        and re-primes from history (or zeros) on its next step."""
        self._slots = fc.init_slots(self.num_slots)
        self._lanes.clear()
        self._lane_stamp.clear()
        self._lane_last_used.clear()
        self._free = list(reversed(range(self.num_slots)))

    def _generate_chunk_locked(self, fc, items, chunk, version,
                               results) -> None:
        import numpy as np

        shape, dtype = input_spec(fc)
        xs = np.zeros((self.num_slots,) + shape, dtype)
        lanes = []
        for idx in chunk:
            cid, x_t, history = items[idx]
            hist = self._clamp_history(fc, history)
            lane = self._ensure_resident_locked(fc, cid, hist, version)
            xs[lane] = np.asarray(x_t, dtype).reshape(shape)
            lanes.append(lane)
        try:
            ys, ps, _ = fc.generate(self._slots, xs, lanes=lanes)
        except Exception:
            if _donate_default():
                # the donating generate may have consumed the slot
                # state before failing — poisoned lanes would corrupt
                # every resident session, so reset the whole plane
                self._reset_slots_locked(fc)
            raise
        lengths = getattr(self._slots, "lengths", None)
        if lengths is not None:
            self.kv_tokens_read += int(lengths[lanes].sum())
        self.ssm_inplace_waves += bool(getattr(fc, "ssm_inplace", False))
        for row, idx in enumerate(chunk):
            results[idx] = (float(ys[lanes[row]]), float(ps[lanes[row]]))

    def spill(self, client_ids=None) -> int:
        """Spill lane-resident sessions (all, or just ``client_ids``)
        into the cache — the migration/export path: after a spill the
        cache's ``export`` sees every session, carries bitwise-identical
        to the lane state. Returns the number of sessions spilled."""
        fc = self._resolve()
        if isinstance(client_ids, str):
            client_ids = [client_ids]
        with self._slots_lock:
            if client_ids is None:
                ids = list(self._lanes)
            else:
                ids = [c for c in client_ids if c in self._lanes]
            for cid in ids:
                lane = self._lanes.pop(cid)
                carry = fc.extract(self._slots, lane)
                self.cache.put(cid, carry, self._nbytes,
                               version=self._lane_stamp.pop(cid))
                self._lane_last_used.pop(cid, None)
                fc.release(self._slots, lane)
                self._free.append(lane)
                self.slot_spills += 1
            tel = self.cache.telemetry
            if ids and tel is not None:
                tel.record_slots(spills=len(ids),
                                 active=len(self._lanes),
                                 lanes=self.num_slots)
            return len(ids)

    def spill_all(self) -> int:
        return self.spill(None)

    def resident_clients(self) -> list[str]:
        """Client ids currently occupying a device lane."""
        with self._slots_lock:
            return list(self._lanes)

    def lanes(self) -> dict[str, int]:
        """The lane of each lane-resident session (a snapshot)."""
        with self._slots_lock:
            return dict(self._lanes)

    def slot_stats(self) -> dict:
        with self._slots_lock:
            return {"lanes": self.num_slots,
                    "active": len(self._lanes),
                    "inserts": self.slot_inserts,
                    "spills": self.slot_spills,
                    "expiries": self.slot_expiries,
                    "kv_tokens_read": self.kv_tokens_read,
                    "ssm_inplace_waves": self.ssm_inplace_waves,
                    "lane_state_bytes": dict(self.lane_state_bytes)}
