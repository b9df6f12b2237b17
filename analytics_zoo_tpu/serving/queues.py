"""Queue backends for serving.

The reference's data plane is a Redis stream (``image_stream`` XADD /
consumer-group reads, results in a ``result:<uri>`` hash —
``ClusterServing.scala:106-140,276-307``; client ``client.py:62,131``).
Here the backend is pluggable:

- :class:`FileQueue` (default): a spool directory — zero extra
  dependencies, works single-host and on a shared filesystem across hosts
  (results as per-uri JSON files). Requests are claimed by atomic rename
  locally, and by exclusive-create claim markers on ``scheme://`` spools
  (remote renames are copy+delete, not atomic); exactly-once on remote
  spools is as strong as the backend's exclusive-create (see
  ``file_io.create_exclusive``) — use RedisQueue for a hard guarantee.
- :class:`RedisQueue`: the reference's wire contract (stream + hash), gated
  on the ``redis`` package being installed.
"""
from __future__ import annotations

import base64
import hashlib
import json
import os
import threading

from ..common import file_io
from ..common.utils import wall_clock
import uuid
from typing import Any, Dict, List, Optional, Sequence, Tuple

#: admission classes, in CLAIM priority order — critical requests are
#: claimed first; shed/trim consume the lanes in the REVERSE order, so
#: sheddable traffic absorbs overload before default, and default before
#: critical (docs/serving.md#overload-survival)
CRITICALITY_LANES = ("critical", "default", "sheddable")
_CLAIM_RANK = {lane: i for i, lane in enumerate(CRITICALITY_LANES)}
_SHED_ORDER = tuple(reversed(CRITICALITY_LANES))
_SHED_RANK = {lane: i for i, lane in enumerate(_SHED_ORDER)}
#: FileQueue filename lane tag ("{ts}-{uuid}.{tag}.json")
_LANE_TAG = {"critical": "c", "default": "d", "sheddable": "s"}
_TAG_LANE = {v: k for k, v in _LANE_TAG.items()}


def criticality_of(payload: Dict[str, Any]) -> str:
    """The request's admission class; unknown/absent values degrade to
    ``default`` (never an error — a foreign producer must not crash
    admission control)."""
    lane = payload.get("criticality")
    return lane if lane in _CLAIM_RANK else "default"


class QueueBackend:
    """enqueue/claim requests; put/get results."""

    def enqueue(self, uri: str, payload: Dict[str, Any]) -> None:
        raise NotImplementedError

    def enqueue_many(self, items: Sequence[Tuple[str, Dict[str, Any]]]
                     ) -> None:
        """Enqueue a batch of ``(uri, payload)`` records. Backends override
        this with an amortized publish (one rename / one pipeline round-trip
        per batch); the default is the per-record loop."""
        for uri, payload in items:
            self.enqueue(uri, payload)

    def claim_batch(self, max_items: int) -> List[Tuple[str, Dict[str, Any]]]:
        """Atomically claim up to ``max_items`` pending requests."""
        raise NotImplementedError

    def put_result(self, uri: str, value: Dict[str, Any]) -> None:
        raise NotImplementedError

    def get_result(self, uri: str) -> Optional[Dict[str, Any]]:
        raise NotImplementedError

    def pending_count(self) -> int:
        raise NotImplementedError

    def trim(self, max_pending: int) -> int:
        """Drop oldest requests beyond ``max_pending`` (the redis maxmem
        xtrim guard, ClusterServing.scala:134-140). Returns dropped count.
        SILENT — the dropped clients poll to their timeout. Kept for
        direct queue administration; the serve loop uses :meth:`shed`."""
        raise NotImplementedError

    def shed(self, max_pending: int,
             reason: str = "shed: queue overloaded") -> List[str]:
        """Erroring admission control: atomically remove requests beyond
        ``max_pending`` and post a terminal ``{"error": reason,
        "retriable": True}`` result for each, so every dropped client
        gets an explicit answer instead of polling to its timeout.
        Victims are consumed criticality-lane-first (sheddable, then
        default, then critical; oldest first within a lane), so under
        overload the critical class is the last to lose work.
        Returns the shed uris. Claims are exclusive — on a shared spool N
        servers shedding concurrently drop each request at most once."""
        raise NotImplementedError

    def discard_result(self, uri: str) -> bool:
        """Drop ``uri``'s terminal result from the result store, if any.
        Used by the client's hedged query to reap the losing copy so it
        is never surfaced and never leaks storage. Returns True when a
        result record was removed."""
        return False


class FileQueue(QueueBackend):
    # a remote claim marker older than this is considered abandoned (the
    # claiming consumer died between claim and cleanup) and is reaped so
    # the record becomes claimable again — at-least-once past a crash, the
    # same recovery stance as redis XAUTOCLAIM
    CLAIM_LEASE_S = 300.0

    def __init__(self, root: str, claim_lease_s: Optional[float] = None,
                 results_root: Optional[str] = None):
        """``results_root`` detaches the result store from the request
        spool: the fleet tier gives every server its OWN request spool
        (``<root>/inst/<name>``) while all of them post results into the
        FRONT spool's ``results/`` — clients poll one place no matter
        which instance answered, and the router's re-routing stays
        invisible to them."""
        self.root = root
        self.req_dir = file_io.join(root, "requests")
        self.claim_dir = file_io.join(root, "claimed")
        self.res_dir = file_io.join(results_root if results_root else root,
                                    "results")
        self.claim_lease_s = (claim_lease_s if claim_lease_s is not None
                              else self.CLAIM_LEASE_S)
        for d in (self.req_dir, self.claim_dir, self.res_dir):
            file_io.makedirs(d, exist_ok=True)

    @staticmethod
    def _record_name(payload: Dict[str, Any]) -> str:
        """Spool filename: wall-clock stamp (FIFO within a lane under
        ``sorted()``) + uniquifier + criticality lane tag, so claim/shed
        ordering never has to open the record to learn its class."""
        tag = _LANE_TAG[criticality_of(payload)]
        return (f"{int(wall_clock() * 1e9):020d}-"
                f"{uuid.uuid4().hex[:8]}.{tag}.json")

    @staticmethod
    def _lane_of_name(name: str) -> str:
        parts = name.split(".")
        if len(parts) >= 3 and parts[-2] in _TAG_LANE:
            return _TAG_LANE[parts[-2]]
        return "default"  # pre-lane spool files keep working

    def enqueue(self, uri: str, payload: Dict[str, Any]) -> None:
        name = self._record_name(payload)
        tmp = file_io.join(self.req_dir, "." + name)
        with file_io.fopen(tmp, "w") as f:
            f.write(json.dumps({"uri": uri, **payload}))
        file_io.replace(tmp, file_io.join(self.req_dir, name))  # atomic publish

    def enqueue_many(self, items: Sequence[Tuple[str, Dict[str, Any]]]
                     ) -> None:
        """Batched publish: all records are written into a hidden staging
        dir and made visible with ONE directory rename — a streaming
        producer pays one atomic publish per batch instead of one
        tmp-write + rename per record. Consumers flatten published batch
        dirs back into the spool lazily (see :meth:`_flatten_batches`).
        Remote spools rename by copy+delete (not atomic), so they fall
        back to the per-record loop."""
        items = list(items)
        if not items:
            return
        if file_io.is_remote(self.req_dir):
            for uri, payload in items:
                self.enqueue(uri, payload)
            return
        stage = file_io.join(self.req_dir, f".stage-{uuid.uuid4().hex[:8]}")
        file_io.makedirs(stage, exist_ok=True)
        for uri, payload in items:
            name = self._record_name(payload)
            with file_io.fopen(file_io.join(stage, name), "w") as f:
                f.write(json.dumps({"uri": uri, **payload}))
        batch = file_io.join(
            self.req_dir,
            f"batch-{int(wall_clock() * 1e9):020d}-{uuid.uuid4().hex[:8]}")
        file_io.replace(stage, batch)  # one rename publishes the batch

    def _flatten_batches(self, names: List[str]) -> List[str]:
        """Expand ``batch-*`` dirs published by :meth:`enqueue_many` into
        top-level record files and return the claimable names. Each member
        move is an atomic rename, so a consumer crashing mid-flatten
        leaves the rest claimable by the next lister; concurrent
        flatteners race per file and the loser skips (same stance as
        claims)."""
        out = [n for n in names if not n.startswith("batch-")]
        for bname in names:
            if not bname.startswith("batch-"):
                continue
            bdir = file_io.join(self.req_dir, bname)
            try:
                members = file_io.listdir(bdir, refresh=True)
            except (FileNotFoundError, NotADirectoryError, OSError):
                continue
            for m in members:
                try:
                    file_io.replace(file_io.join(bdir, m),
                                    file_io.join(self.req_dir, m))
                    out.append(m)
                except (OSError, FileNotFoundError):
                    pass  # another consumer moved it first
            try:
                # drop the dir only once it is verifiably empty — a move
                # that failed for any reason other than losing a race
                # must leave its record claimable on the next pass
                if not file_io.listdir(bdir, refresh=True):
                    file_io.rmtree(bdir)
            except (OSError, FileNotFoundError):
                pass
        return out

    def _claim_one(self, name: str) -> Optional[str]:
        """Claim one request; returns the path to read it from, or None if
        another consumer won. Local spools claim by atomic rename
        (os.replace — the loser raises). Remote spools claim by an
        EXCLUSIVE-CREATE marker in claimed/: a remote ``replace`` is
        copy+delete, so two consumers could both 'win' a rename — the
        marker makes the winner explicit (see file_io.create_exclusive for
        the per-backend atomicity story)."""
        src = file_io.join(self.req_dir, name)
        if not file_io.is_remote(src):
            dst = file_io.join(self.claim_dir, name)
            try:
                file_io.replace(src, dst)  # atomic claim; loser raises
            except (OSError, FileNotFoundError):
                return None
            return dst
        marker = file_io.join(self.claim_dir, name + ".claim")
        try:
            file_io.create_exclusive(
                marker, repr(wall_clock()).encode())
        except (FileExistsError, OSError):
            # marker held by another consumer — unless it's an expired
            # lease from a consumer that died between claim and cleanup.
            # Reaping (remove + recreate) is NOT atomic, so two reapers
            # interleaving could both "win" their create_exclusive (B
            # creates fresh, C removes B's fresh marker and creates its
            # own); a reap LOCK serializes them: only the exclusive-create
            # winner of ``<marker>.reap`` may remove and recreate the
            # claim marker.
            def _read_raw(path):
                try:
                    with file_io.fopen(path, "rb") as f:
                        return f.read().decode()
                except (OSError, FileNotFoundError, ValueError):
                    # ValueError covers UnicodeDecodeError from a corrupt
                    # or foreign marker: treat as unreadable, not fatal —
                    # the poll loop must survive junk in the spool
                    return None

            def _read_stamp(path):
                raw = _read_raw(path)
                if raw is None:  # vanished = claim completed, NOT stale
                    return None
                try:
                    # claim markers hold a bare stamp; reap locks hold
                    # "stamp:token" — the first field is the stamp either way
                    return float(raw.split(":")[0] or 0)
                except ValueError:
                    return None

            stamp = _read_stamp(marker)
            if stamp is None or wall_clock() - stamp < self.claim_lease_s:
                return None
            reap_lock = marker + ".reap"
            # unique stamp doubles as an ownership token: the finally
            # below must not delete a lock some other consumer re-acquired
            # after OUR tenure was (legitimately) declared stale
            lock_token = f"{wall_clock()!r}:{uuid.uuid4().hex}"
            try:
                file_io.create_exclusive(reap_lock, lock_token.encode())
            except (FileExistsError, OSError):
                # another consumer is reaping; if the LOCK itself is stale
                # (its holder died mid-reap), clear it so a later pass can
                # retry. The 2x-lease margin is the standard lease-system
                # stall bound: deleting a LIVE lock here would need the
                # reader to stall >1 full lease between read and remove.
                lock_stamp = _read_stamp(reap_lock)
                if (lock_stamp is not None
                        and wall_clock() - lock_stamp
                        >= 2 * self.claim_lease_s):
                    try:
                        file_io.remove(reap_lock)
                    except (OSError, FileNotFoundError):
                        pass
                return None
            try:
                # RE-VALIDATE under the lock: a previous reaper may have
                # already reclaimed this marker between our staleness read
                # and the lock acquisition — its fresh claim must survive
                stamp = _read_stamp(marker)
                if stamp is None or \
                        wall_clock() - stamp < self.claim_lease_s:
                    return None
                try:
                    file_io.remove(marker)
                except (OSError, FileNotFoundError):
                    pass
                # a fresh (non-reaping) consumer may slip in between the
                # remove and this create — then IT owns the claim and this
                # create fails: exactly one winner either way
                try:
                    file_io.create_exclusive(
                        marker, repr(wall_clock()).encode())
                except (FileExistsError, OSError):
                    return None
            finally:
                # release ONLY if we still own it: a reaper that stalled
                # past the 2x-lease margin may find its lock legitimately
                # cleared and re-acquired by another consumer — deleting
                # that live lock would re-open the two-reaper race
                if _read_raw(reap_lock) == lock_token:
                    try:
                        file_io.remove(reap_lock)
                    except (OSError, FileNotFoundError):
                        pass
        return src

    def _remove_claimed(self, name: str, path: str) -> None:
        """Clean up a fully-consumed claim: request file(s) first, marker
        LAST — a marker removed while the request still exists would let a
        second consumer re-claim the record."""
        cleanup = list({path, file_io.join(self.req_dir, name)})
        if file_io.is_remote(path):
            # the marker must not outlive the request either:
            # remote spools would leak one object per record
            cleanup.append(file_io.join(self.claim_dir, name + ".claim"))
        for p in cleanup:
            try:
                file_io.remove(p)
            except (OSError, FileNotFoundError):
                pass

    def claim_batch(self, max_items: int) -> List[Tuple[str, Dict[str, Any]]]:
        out = []
        try:
            # refresh: another process's enqueues must be visible despite
            # fsspec listing caches (remote spools). Claim order is
            # priority-lane first (critical → default → sheddable), FIFO
            # within a lane — under overload the deadline enforcement at
            # claim time therefore expires sheddable work last-admitted.
            names = sorted(self._flatten_batches(
                file_io.listdir(self.req_dir, refresh=True)),
                key=lambda n: (_CLAIM_RANK[self._lane_of_name(n)], n))
        except FileNotFoundError:
            return out
        for name in names:
            if name.startswith(".") or len(out) >= max_items:
                continue
            path = self._claim_one(name)
            if path is None:
                continue
            try:
                with file_io.fopen(path) as f:
                    rec = json.loads(f.read())
                out.append((rec["uri"], rec))
            except (ValueError, KeyError, OSError):
                # malformed request file (partial write / foreign producer):
                # skip it, keep the batch and the serve loop alive
                import logging
                logging.getLogger("analytics_zoo_tpu.serving").warning(
                    "dropping malformed request file %s", name)
            finally:
                self._remove_claimed(name, path)
        return out

    def shed(self, max_pending: int,
             reason: str = "shed: queue overloaded") -> List[str]:
        try:
            # victim order is the REVERSE of claim priority: sheddable
            # lanes absorb the overload first, critical requests are the
            # last to be dropped (oldest first within a lane)
            names = sorted((n for n in self._flatten_batches(
                file_io.listdir(self.req_dir, refresh=True))
                            if not n.startswith(".")),
                           key=lambda n: (_SHED_RANK[self._lane_of_name(n)],
                                          n))
        except FileNotFoundError:
            return []
        dropped: List[str] = []
        for name in names[:max(0, len(names) - max_pending)]:
            path = self._claim_one(name)  # exclusive: N shedders, one winner
            if path is None:
                continue
            try:
                with file_io.fopen(path) as f:
                    rec = json.loads(f.read())
                self.put_result(rec["uri"],
                                {"error": reason, "retriable": True})
                dropped.append(rec["uri"])
            except (ValueError, KeyError, OSError):
                # malformed request: no uri to answer — drop it outright
                import logging
                logging.getLogger("analytics_zoo_tpu.serving").warning(
                    "dropping malformed request file %s during shed", name)
            finally:
                self._remove_claimed(name, path)
        return dropped

    def put_result(self, uri: str, value: Dict[str, Any]) -> None:
        key = hashlib.md5(uri.encode()).hexdigest()
        # a temporary name a writing thread: a server's publisher and its
        # serve loop (shed) may write at once, and must never share one
        tmp = file_io.join(self.res_dir,
                           f".{key}.{threading.get_ident():x}")
        with file_io.fopen(tmp, "w") as f:
            f.write(json.dumps({"uri": uri, **value}))
        file_io.replace(tmp, file_io.join(self.res_dir, key + ".json"))

    def get_result(self, uri: str) -> Optional[Dict[str, Any]]:
        key = hashlib.md5(uri.encode()).hexdigest()
        path = file_io.join(self.res_dir, key + ".json")
        if not file_io.exists(path):
            return None
        with file_io.fopen(path) as f:
            return json.loads(f.read())

    def discard_result(self, uri: str) -> bool:
        key = hashlib.md5(uri.encode()).hexdigest()
        path = file_io.join(self.res_dir, key + ".json")
        try:
            file_io.remove(path)
            return True
        except (OSError, FileNotFoundError):
            return False

    def all_results(self) -> Dict[str, Dict[str, Any]]:
        out = {}
        for name in file_io.listdir(self.res_dir):
            if name.startswith("."):
                continue
            with file_io.fopen(file_io.join(self.res_dir, name)) as f:
                rec = json.loads(f.read())
            out[rec["uri"]] = rec
        return out

    def pending_count(self) -> int:
        """Backlog depth, counting INTO published-but-unflattened batch
        dirs (read-only — depth accounting must not mutate the spool)."""
        try:
            count = 0
            for n in file_io.listdir(self.req_dir, refresh=True):
                if n.startswith("."):
                    continue
                if n.startswith("batch-"):
                    try:
                        count += sum(
                            1 for m in file_io.listdir(
                                file_io.join(self.req_dir, n), refresh=True)
                            if not m.startswith("."))
                    except (FileNotFoundError, NotADirectoryError, OSError):
                        pass
                else:
                    count += 1
            return count
        except FileNotFoundError:
            return 0

    def trim(self, max_pending: int) -> int:
        names = sorted((n for n in self._flatten_batches(
            file_io.listdir(self.req_dir, refresh=True))
                        if not n.startswith(".")),
                       key=lambda n: (_SHED_RANK[self._lane_of_name(n)], n))
        dropped = 0
        for name in names[:max(0, len(names) - max_pending)]:
            try:
                file_io.remove(file_io.join(self.req_dir, name))
                dropped += 1
            except OSError:
                pass
        return dropped


class RedisQueue(QueueBackend):
    """The reference wire contract: XADD to ``image_stream``, consumer-group
    reads, results HSET at ``result:<uri>``. Needs the redis package.

    Delivery is AT-LEAST-ONCE past a crash: a claimed entry is XACKed only
    after its result lands in :meth:`put_result` — a server that dies
    between claim and result leaves the entry in the group's PEL, and
    :meth:`claim_batch` XAUTOCLAIMs entries idle past ``claim_lease_s``
    back onto a live consumer (the FileQueue claim-marker reaping stance,
    in redis' native vocabulary)."""

    STREAM = "image_stream"
    GROUP = "serving"
    #: a pending entry idle this long belongs to a consumer presumed dead
    CLAIM_LEASE_S = 60.0

    def __init__(self, host: str = "localhost", port: int = 6379,
                 claim_lease_s: Optional[float] = None, client=None,
                 stream: Optional[str] = None, group: Optional[str] = None):
        if client is None:
            import redis  # gated dependency
            client = redis.StrictRedis(host=host, port=port, db=0)
        self.db = client
        if stream:
            self.STREAM = stream  # instance shadow of the class default —
        if group:                 # lets benches/tests run isolated streams
            self.GROUP = group    # on one shared server
        # unique consumer identity per server instance: XREADGROUP '>'
        # delivers each entry to exactly one consumer in the group, which
        # is what makes N serving servers on one stream exactly-once
        # (ClusterServing.scala's multi-executor contract)
        self.consumer = f"consumer-{uuid.uuid4().hex[:12]}"
        self.claim_lease_s = (claim_lease_s if claim_lease_s is not None
                              else self.CLAIM_LEASE_S)
        # criticality lanes are sibling streams sharing one group name:
        # default traffic rides the base stream (the reference wire
        # contract is unchanged), critical/sheddable get their own streams
        # so claim order and shed order can differ per class without
        # opening any payload
        self._lane_streams = {
            "critical": f"{self.STREAM}:crit",
            "default": self.STREAM,
            "sheddable": f"{self.STREAM}:shed",
        }
        # uri -> (stream, entry id), claimed but not yet answered; the ack
        # in put_result closes the loop. claim_batch stores on the serve
        # loop and put_result pops on the writer's thread (ClusterServing's
        # write-back, GenerativeServing's publisher): one store and one pop
        # a uri, each atomic under the interpreter lock, and nothing reads
        # the dict and then acts on what it read
        self._unacked: Dict[str, Tuple[str, Any]] = {}
        for lane in CRITICALITY_LANES:
            try:
                self.db.xgroup_create(self._lane_streams[lane], self.GROUP,
                                      mkstream=True)
            except Exception:
                pass  # group exists

    def enqueue(self, uri: str, payload: Dict[str, Any]) -> None:
        self.db.xadd(self._lane_streams[criticality_of(payload)],
                     {"uri": uri, "data": json.dumps(payload)})

    def enqueue_many(self, items: Sequence[Tuple[str, Dict[str, Any]]]
                     ) -> None:
        """Pipelined XADD: one round-trip per batch instead of one per
        record (order within the batch is preserved — a pipeline executes
        commands in submission order)."""
        items = list(items)
        if not items:
            return
        pipe = self.db.pipeline()
        for uri, payload in items:
            pipe.xadd(self._lane_streams[criticality_of(payload)],
                      {"uri": uri, "data": json.dumps(payload)})
        pipe.execute()

    def _reclaim_stale(self, stream: str, max_items: int) -> List:
        """XAUTOCLAIM entries whose claiming consumer died before acking
        (idle past the lease). Absent on old servers/fakes: no reclaim."""
        try:
            resp = self.db.xautoclaim(
                stream, self.GROUP, self.consumer,
                min_idle_time=int(self.claim_lease_s * 1000.0),
                count=max_items)
        except Exception:
            return []
        # redis-py returns (next_id, entries[, deleted]) depending on
        # server version; the entry list is always the second field
        if isinstance(resp, (list, tuple)) and len(resp) >= 2:
            return list(resp[1] or [])
        return []

    def claim_batch(self, max_items: int) -> List[Tuple[str, Dict[str, Any]]]:
        out: List[Tuple[str, Dict[str, Any]]] = []
        # priority lanes: drain the critical stream before default before
        # sheddable, FIFO within each
        for lane in CRITICALITY_LANES:
            room = max_items - len(out)
            if room <= 0:
                break
            stream = self._lane_streams[lane]
            entries = self._reclaim_stale(stream, room)
            if len(entries) < room:
                resp = self.db.xreadgroup(self.GROUP, self.consumer,
                                          {stream: ">"},
                                          count=room - len(entries),
                                          block=10)
                for _, fresh in resp or []:
                    entries.extend(fresh)
            for eid, fields in entries:
                uri = fields[b"uri"].decode()
                payload = json.loads(fields[b"data"].decode())
                out.append((uri, {"uri": uri, **payload}))
                # at-most-once fix: NO xack here — the ack waits for the
                # result (put_result), so a crash mid-batch redelivers via
                # _reclaim_stale instead of dropping the request forever
                self._unacked[uri] = (stream, eid)
        return out

    def put_result(self, uri: str, value: Dict[str, Any]) -> None:
        self.db.hset(f"result:{uri}", mapping={
            k: json.dumps(v) for k, v in value.items()})
        claim = self._unacked.pop(uri, None)
        if claim is not None:
            # result durable → the claim is settled; ack AFTER the hset so
            # a crash between the two redelivers (result overwrite is
            # idempotent) rather than losing the request
            stream, eid = claim
            self.db.xack(stream, self.GROUP, eid)

    def get_result(self, uri: str) -> Optional[Dict[str, Any]]:
        raw = self.db.hgetall(f"result:{uri}")
        if not raw:
            return None
        return {k.decode(): json.loads(v.decode()) for k, v in raw.items()}

    def discard_result(self, uri: str) -> bool:
        try:
            return bool(self.db.delete(f"result:{uri}"))
        except Exception:
            return False

    def _stream_pending(self, stream: str) -> int:
        # undelivered backlog (group lag) when the server exposes it —
        # XLEN counts already-served entries that linger until an XTRIM
        # and would make admission control shed phantom load
        try:
            for g in self.db.xinfo_groups(stream):
                name = g.get("name")
                if name in (self.GROUP, self.GROUP.encode()):
                    lag = g.get("lag")
                    if lag is not None:
                        return int(lag)
        except Exception:
            pass
        try:
            return int(self.db.xlen(stream))
        except Exception:
            return 0

    def pending_count(self) -> int:
        return sum(self._stream_pending(self._lane_streams[lane])
                   for lane in CRITICALITY_LANES)

    def consumer_pending(self) -> Dict[str, int]:
        """Per-consumer pending (claimed-not-yet-acked) counts, via XINFO
        CONSUMERS, summed across the lane streams. Group lag
        (:meth:`pending_count`) is the UNDELIVERED backlog; this is the
        in-flight side — what each server instance has claimed and not yet
        answered. The fleet router reads it as the true per-instance queue
        depth a placement decision adds to. Returns ``{}`` when the
        server/fake doesn't support the call."""
        out: Dict[str, int] = {}
        ok = False
        for lane in CRITICALITY_LANES:
            try:
                consumers = self.db.xinfo_consumers(
                    self._lane_streams[lane], self.GROUP)
            except Exception:
                continue
            ok = True
            for c in consumers:
                name = c.get("name")
                if isinstance(name, bytes):
                    name = name.decode()
                if name is None:
                    continue
                out[str(name)] = (out.get(str(name), 0)
                                  + int(c.get("pending") or 0))
        return out if ok else {}

    def trim(self, max_pending: int) -> int:
        before = self.pending_count()
        excess = before - max_pending
        for lane in _SHED_ORDER:  # sheddable lanes absorb the cut first
            if excess <= 0:
                break
            stream = self._lane_streams[lane]
            depth = self._stream_pending(stream)
            cut = min(excess, depth)
            if cut > 0:
                self.db.xtrim(stream, maxlen=depth - cut)
                excess -= cut
        return max(0, before - self.pending_count())

    def shed(self, max_pending: int,
             reason: str = "shed: queue overloaded") -> List[str]:
        dropped: List[str] = []
        excess = self.pending_count() - max_pending
        for lane in _SHED_ORDER:  # sheddable victims first, critical last
            while excess > 0:
                stream = self._lane_streams[lane]
                resp = self.db.xreadgroup(self.GROUP, self.consumer,
                                          {stream: ">"}, count=excess,
                                          block=10)
                entries = [e for _, es in resp or [] for e in es]
                if not entries:
                    break
                for eid, fields in entries:
                    uri = fields[b"uri"].decode()
                    self.put_result(uri,
                                    {"error": reason, "retriable": True})
                    self.db.xack(stream, self.GROUP, eid)
                    dropped.append(uri)
                excess -= len(entries)
        return dropped


def make_queue(src: str) -> QueueBackend:
    """``dir:///path``, a path, or a ``scheme://`` URI → FileQueue;
    ``host:port`` → RedisQueue."""
    if src.startswith("dir://"):
        return FileQueue(src[len("dir://"):])
    if file_io.scheme_of(src) is not None:
        return FileQueue(src)
    if ":" in src and not os.sep in src.split(":")[0]:
        host, port = src.rsplit(":", 1)
        try:
            return RedisQueue(host, int(port))
        except ImportError as e:
            raise RuntimeError(
                f"queue src '{src}' needs the redis package; use a "
                f"dir:///path file queue instead") from e
    return FileQueue(src)


def encode_image(img) -> str:
    """ndarray/bytes → base64 jpg string (client-side payload encoding)."""
    import numpy as np
    if isinstance(img, (bytes, bytearray)):
        return base64.b64encode(bytes(img)).decode()
    import cv2
    ok, buf = cv2.imencode(".jpg", np.asarray(img))
    if not ok:
        raise ValueError("image encode failed")
    return base64.b64encode(buf.tobytes()).decode()


def decode_image(b64: str):
    import cv2
    import numpy as np
    buf = np.frombuffer(base64.b64decode(b64), np.uint8)
    img = cv2.imdecode(buf, cv2.IMREAD_COLOR)
    if img is None:
        raise ValueError("image decode failed")
    return img
