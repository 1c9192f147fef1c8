"""`eventlog` storage backend: native append-only binary log for EVENTDATA.

The TPU-native analogue of the reference's HBase backend (EVENTDATA only —
storage/hbase/.../HBEvents.scala): a high-throughput event store whose scan
path runs in native code. Events append to one ``PIOLOG01`` file per
app/channel (format: native/format.py); reads go through the C++ scanner
(native/src/eventlog.cc) when built, with a pure-Python mirror otherwise —
both paths produce identical results (tested in tests/test_native_eventlog.py).

Config (``PIO_STORAGE_SOURCES_<NAME>_...``):

- ``TYPE=eventlog``
- ``PATH=<directory>`` — where the per-app log files live.

Like the reference's HBase backend it serves EVENTDATA only; combine with
``sqlite`` for METADATA/MODELDATA in ``PIO_STORAGE_REPOSITORIES_*``.
"""

from __future__ import annotations

import datetime as _dt
import fcntl
import logging
import os
import threading
import uuid
from typing import Any, Optional, Sequence

from incubator_predictionio_tpu.data.event import Event, PropertyMap
from incubator_predictionio_tpu.data.storage.base import (
    UNSET,
    EventStore,
    StorageClient,
    StorageError,
)
from incubator_predictionio_tpu.data.storage.registry import register_backend
from incubator_predictionio_tpu.native import (
    assemble as native_assemble,
    fold as native_fold,
    make_filter,
    scan as native_scan,
)
from incubator_predictionio_tpu.native import format as fmt

logger = logging.getLogger(__name__)


class ReadOnlyLogError(StorageError):
    """A write hit a log opened read-only (another process holds the
    writer flock, or this store is a replication follower). Distinct from
    plain :class:`StorageError` because the condition is TRANSIENT
    cluster-wise — a role flip or failover resolves it — so the storage
    server answers 503 (retry/spill) instead of a semantic 500 that would
    send acked events to the dead-letter segment."""


class _Log:
    """One open log file: append handle + in-memory id index + string table.

    Single-writer: an exclusive advisory lock (flock) is held on the append
    handle for its lifetime, so a second writer — another process, or another
    store over the same directory — fails fast instead of corrupting the
    intern table (writers assign intern ids from their own in-memory count).
    Readers never take the lock: a ``read_only`` log keeps no append handle
    and refreshes its in-memory index whenever the file changes on disk —
    that's how a trainer process reads while the event server (the one
    writer) stays live, the topology the reference gets for free from its
    database services.
    """

    def __init__(self, path: str, read_only: bool = False):
        self.path = path
        self.lock = threading.RLock()
        self.interner = fmt.Interner()
        self.strings: dict[int, str] = {}
        self.index: dict[str, int] = {}  # live event_id -> record offset
        self.read_only = read_only
        if read_only:
            self.f = None
            self._ro_end = 0  # absolute offset of the next unparsed byte
            self._ro_tail = b""  # last bytes ending at _ro_end (regrow detector)
            self._ro_stat = None  # (st_size, st_mtime_ns) at last refresh
            self.refresh()
            return
        existed = os.path.exists(path)
        self.f = open(path, "ab")
        try:
            fcntl.flock(self.f.fileno(), fcntl.LOCK_EX | fcntl.LOCK_NB)
        except OSError:
            self.f.close()
            raise StorageError(
                f"event log {path} is locked by another writer "
                "(eventlog is single-writer; route writes through one "
                "event server / store instance)"
            )
        if existed:
            with open(path, "rb") as rf:
                buf = rf.read()
            if len(buf) == 0:
                existed = False  # crash before the magic was written
        if existed:
            self.strings, self.index, _ = fmt.read_log(buf)
            self.interner.ids = {s: i for i, s in self.strings.items()}
            # A crash can leave a torn/zeroed tail. Scanners skip it, but new
            # appends would land AFTER the garbage and be unreachable — so
            # truncate back to the end of the last valid record.
            valid_end = fmt.valid_extent(buf)
            if valid_end < len(buf):
                self.f.truncate(valid_end)
                self.f.seek(valid_end)
        if self.f.tell() == 0:
            self.f.write(fmt.MAGIC)
            self.f.flush()

    def refresh(self) -> None:
        """Writer: flush appends to disk. Read-only: fold newly appended
        records into the in-memory index/string table (the writer lives
        elsewhere). The format is append-only, so only the suffix past the
        last complete record is read and parsed — a previously torn tail is
        retried from the same offset once the writer completes it."""
        with self.lock:
            if self.f is not None:
                self.f.flush()
                return
            try:
                st = os.stat(self.path)
            except FileNotFoundError:
                return
            size = st.st_size
            sig = (st.st_size, st.st_mtime_ns)
            if sig == self._ro_stat and size <= self._ro_end:
                # Same stat signature since last refresh (the common case for
                # point reads, which call refresh() per record). The stat
                # alone can miss a truncate-then-regrow to the identical size
                # within one mtime granule, so still verify the tail bytes —
                # one small pread, no magic re-check / full reparse.
                if self._ro_tail and _pread(
                    self.path, self._ro_end - len(self._ro_tail),
                    len(self._ro_tail),
                ) == self._ro_tail:
                    return
                # tail moved under an unchanged stat → fall through to the
                # full (rebuilding) path
                self._ro_stat = None
            if size < self._ro_end:
                # File shrank: a recovering writer truncated a torn tail that
                # we may have (mis)parsed as complete records. Our index can
                # hold offsets past the new EOF, and `size <= _ro_end` would
                # suppress refreshes forever — rebuild the view from scratch.
                self._reset_ro_view()
            if self._ro_end == 0 and size < len(fmt.MAGIC):
                return
            with open(self.path, "rb") as rf:
                magic = rf.read(len(fmt.MAGIC))
                if magic != fmt.MAGIC:
                    raise StorageError(f"{self.path} is not a PIOLOG01 file")
                if self._ro_end == 0:
                    self._ro_end = len(fmt.MAGIC)
                    self._ro_tail = fmt.MAGIC
                elif self._ro_tail:
                    # Truncate-then-REGROW leaves size >= _ro_end while the
                    # bytes under our offset changed; verify the tail snapshot
                    # before trusting the offset.
                    rf.seek(self._ro_end - len(self._ro_tail))
                    if rf.read(len(self._ro_tail)) != self._ro_tail:
                        self._reset_ro_view()
                        self._ro_end = len(fmt.MAGIC)
                        self._ro_tail = fmt.MAGIC
                if size <= self._ro_end:
                    self._ro_stat = sig
                    return
                rf.seek(self._ro_end)
                chunk = rf.read()
            old_end = self._ro_end
            self._ro_end = fmt.apply_records(
                chunk, old_end, self.strings, self.index
            )
            consumed = self._ro_end - old_end
            self._ro_tail = (self._ro_tail + chunk[:consumed])[-32:]
            self._ro_stat = sig

    def _reset_ro_view(self) -> None:
        self._ro_end = 0
        self._ro_tail = b""
        self._ro_stat = None
        self.strings = {}
        self.index = {}

    def _require_writer(self) -> None:
        if self.f is None:
            raise ReadOnlyLogError(
                f"event log {self.path} opened read-only (another process "
                "holds the writer lock, or this store is a replication "
                "follower); route writes through the writer/primary"
            )

    def append_event(self, event: Event, event_id: str) -> None:
        self.append_events([(event, event_id)])

    def append_events(self, pairs: "Sequence[tuple[Event, str]]") -> None:
        """Group commit: encode every record, ONE write + ONE flush for the
        whole batch (the per-event flush was the round-3 ingestion wall —
        a 50-event batch paid 50 kernel round trips for one page of data)."""
        self._require_writer()
        with self.lock:
            off_base = self.f.tell()
            chunks: list[bytes] = []
            offsets: list[tuple[str, int]] = []  # event_id -> record offset
            pos = 0
            for event, event_id in pairs:
                blob = fmt.encode_event(event, event_id, self.interner)
                # the EVENT record is the last record in the blob; find its
                # offset by replaying lengths (INTERN records may precede it)
                p, last = 0, 0
                while p < len(blob):
                    (plen,) = fmt.struct.unpack_from("<I", blob, p)
                    last = p
                    p += 4 + plen
                chunks.append(blob)
                offsets.append((event_id, off_base + pos + last))
                pos += len(blob)
            self.f.write(b"".join(chunks))
            self.f.flush()
            for event_id, off in offsets:
                self.index[event_id] = off
            # mirror the interner into the id->string view
            for s, i in self.interner.ids.items():
                self.strings.setdefault(i, s)

    def append_tombstone(self, event_id: str) -> None:
        self._require_writer()
        with self.lock:
            self.f.write(fmt.encode_tombstone(event_id))
            self.f.flush()
            self.index.pop(event_id, None)

    def read_at(self, offset: int) -> Event:
        with self.lock:
            self.refresh()
            with open(self.path, "rb") as f:
                f.seek(offset)
                head = f.read(4)
                (plen,) = fmt.struct.unpack_from("<I", head, 0)
                payload = f.read(plen)
            _, event = fmt.decode_event_payload(payload, self.strings)
            return event

    def close(self) -> None:
        with self.lock:
            if self.f is not None:
                self.f.close()


def _pread(path: str, offset: int, n: int) -> bytes:
    with open(path, "rb") as f:
        f.seek(max(offset, 0))
        return f.read(n)


class EventLogEvents(EventStore):
    def __init__(self, base_dir: str, read_only: bool = False):
        self.base_dir = base_dir
        os.makedirs(base_dir, exist_ok=True)
        self._logs: dict[tuple[int, Optional[int]], _Log] = {}
        self._lock = threading.RLock()
        # replication follower mode (replication/manager.py): every log
        # opens as a lock-free read-only view, never a flock'd writer —
        # the replicated appends own the files, and a writer opened here
        # would both block them and truncate "torn" tails that are really
        # just chunks still in flight
        self._read_only = read_only

    def _path(self, app_id: int, channel_id: Optional[int]) -> str:
        name = f"app_{app_id}" + (f"_{channel_id}" if channel_id is not None else "")
        return os.path.join(self.base_dir, name + ".piolog")

    def log_path(self, app_id: int, channel_id: Optional[int] = None) -> str:
        """Path of the append-only log file for one app/channel — the
        durable ordered change feed the streaming updater tails
        (streaming/feed.py). Read-only consumers open the file themselves;
        the single-writer flock stays with the event server."""
        return self._path(app_id, channel_id)

    def _log(self, app_id: int, channel_id: Optional[int], create: bool = False) -> _Log:
        key = (app_id, channel_id)
        with self._lock:
            log = self._logs.get(key)
            if log is None:
                path = self._path(app_id, channel_id)
                if not create and not os.path.exists(path):
                    raise StorageError(
                        f"event log for app {app_id} channel {channel_id} not initialized"
                    )
                if self._read_only:
                    log = _Log(path, read_only=True)
                else:
                    try:
                        log = _Log(path)
                    except StorageError:
                        # another process (the event server) holds the writer
                        # lock — serve reads from a lock-free read-only view
                        log = _Log(path, read_only=True)
                self._logs[key] = log
            return log

    def set_read_only(self, read_only: bool) -> None:
        """Flip follower mode (replication role changes). Open logs are
        dropped so the next access re-opens in the new mode — a promotion
        re-acquires writer flocks, a demotion releases them."""
        with self._lock:
            self._read_only = read_only
            self.reopen()

    def reopen(self) -> None:
        """Close and forget every open log so the next access re-reads
        disk state from scratch. Used on replication role changes and
        after an anti-entropy repair patched bytes a cached view may have
        already parsed."""
        with self._lock:
            for log in self._logs.values():
                log.close()
            self._logs.clear()

    # -- lifecycle --------------------------------------------------------
    def init(self, app_id: int, channel_id: Optional[int] = None) -> bool:
        self._log(app_id, channel_id, create=True)
        return True

    def remove(self, app_id: int, channel_id: Optional[int] = None) -> bool:
        key = (app_id, channel_id)
        with self._lock:
            log = self._logs.pop(key, None)
            if log is not None:
                log.close()
            path = self._path(app_id, channel_id)
            if os.path.exists(path):
                os.remove(path)
                return True
            return False

    def close(self) -> None:
        with self._lock:
            for log in self._logs.values():
                log.close()
            self._logs.clear()

    # -- CRUD -------------------------------------------------------------
    def ingest_raw(
        self,
        body: bytes,
        single: bool,
        max_items: int,
        whitelist: Sequence[str],
        app_id: int,
        channel_id: Optional[int] = None,
    ):
        """C ingest fast path: raw request body → parse→validate→encode in
        native code (native/src/ingest.cc), then ONE append+flush of the
        pre-encoded records. Returns the per-item response dicts the event
        server would have produced (parity: EventServer.scala:376-462 via
        server/event_server.py _ingest_batch), or ``None`` when the caller
        must run the Python path (native lib unavailable, read-only log, or
        the C core declined a construct it can't guarantee byte-parity on).

        The whole C call happens under the log's write lock: interner ids
        are assigned inside the C core from a snapshot of the writer's
        string table, so the snapshot → encode → append must be atomic."""
        from incubator_predictionio_tpu import native

        if native.get_lib() is None:
            return None
        log = self._log(app_id, channel_id, create=True)
        if log.f is None:  # read-only view: the Python path raises properly
            return None
        with log.lock:
            # interner snapshot ordered by id (ids are dense, 0..n-1)
            interned = [None] * len(log.interner.ids)
            for s, i in log.interner.ids.items():
                interned[i] = s
            r = native.ingest(body, single, max_items, list(whitelist), interned)
            if r is None or r is native.INGEST_FALLBACK:
                return None
            results, new_strings, offsets, blob = r
            off_base = log.f.tell()
            if blob:
                log.f.write(blob)
                log.f.flush()
            acc = iter(offsets)
            for status, _msg, event_id in results:
                if status == 201:
                    log.index[event_id] = off_base + next(acc)
            for s in new_strings:
                i = len(log.interner.ids)
                log.interner.ids[s] = i
                log.strings.setdefault(i, s)
        return native.results_to_response_dicts(results)

    def insert(self, event: Event, app_id: int, channel_id: Optional[int] = None) -> str:
        return self.insert_batch([event], app_id, channel_id)[0]

    def insert_batch(
        self, events: Sequence[Event], app_id: int, channel_id: Optional[int] = None
    ) -> list[str]:
        log = self._log(app_id, channel_id, create=True)
        pairs = []
        for event in events:
            # urandom hex: same 32-char opaque id, ~5x cheaper than uuid4
            event_id = event.event_id or os.urandom(16).hex()
            pairs.append((event.with_id(event_id), event_id))
        log.append_events(pairs)
        return [event_id for _, event_id in pairs]

    def get(self, event_id: str, app_id: int, channel_id: Optional[int] = None) -> Optional[Event]:
        try:
            log = self._log(app_id, channel_id)
        except StorageError:
            return None
        log.refresh()  # read-only views pick up the writer's appends
        off = log.index.get(event_id)
        if off is None:
            return None
        return log.read_at(off)

    def delete(self, event_id: str, app_id: int, channel_id: Optional[int] = None) -> bool:
        try:
            log = self._log(app_id, channel_id)
        except StorageError:
            return False
        log._require_writer()  # a stale read-only index must not answer False
        if event_id not in log.index:
            return False
        log.append_tombstone(event_id)
        return True

    # -- queries ----------------------------------------------------------
    def find(
        self,
        app_id: int,
        channel_id: Optional[int] = None,
        start_time: Optional[_dt.datetime] = None,
        until_time: Optional[_dt.datetime] = None,
        entity_type: Optional[str] = None,
        entity_id: Optional[str] = None,
        event_names: Optional[Sequence[str]] = None,
        target_entity_type: Any = UNSET,
        target_entity_id: Any = UNSET,
        limit: Optional[int] = None,
        reversed: bool = False,
    ):
        log = self._log(app_id, channel_id)
        flt = make_filter(
            start_time,
            until_time,
            entity_type,
            entity_id,
            event_names,
            _UNSET_MAP(target_entity_type),
            _UNSET_MAP(target_entity_id),
        )
        with log.lock:
            log.refresh()
            hits = native_scan(log.path, flt)
            # refresh again AFTER the scan: a live writer may have interned
            # new strings between our refresh and the scanner's own file
            # read — every id a scanned event references is in the file by
            # then (intern records precede their event), so this re-read
            # makes log.strings sufficient to decode every hit
            log.refresh()
        if hits is not None:
            # the native scanner did the full pass; decode only the chosen
            # hits via seek+read (a limit-N query touches N records, not the
            # whole log)
            hits.sort(key=lambda h: (h[1], h[0]), reverse=reversed)
            if limit is not None and limit >= 0:
                hits = hits[:limit]
            with open(log.path, "rb") as f:
                for off, _ in hits:
                    f.seek(off)
                    (plen,) = fmt.struct.unpack_from("<I", f.read(4), 0)
                    _, event = fmt.decode_event_payload(f.read(plen), log.strings)
                    yield event
            return
        # pure-Python mirror of the native scan: one full read + decode
        with open(log.path, "rb") as f:
            buf = f.read()
        strings, live, _ = fmt.read_log(buf)
        live_offsets = set(live.values())
        start_us = fmt.time_to_us(start_time) if start_time else None
        until_us = fmt.time_to_us(until_time) if until_time else None
        names = set(event_names) if event_names else None
        out: list[tuple[int, int, Event]] = []
        for off, kind, payload in fmt.iter_records(buf):
            if kind != fmt.KIND_EVENT or off not in live_offsets:
                continue
            _, e = fmt.decode_event_payload(payload, strings)
            t_us = fmt.time_to_us(e.event_time)
            if start_us is not None and t_us < start_us:
                continue
            if until_us is not None and t_us >= until_us:
                continue
            if entity_type is not None and e.entity_type != entity_type:
                continue
            if entity_id is not None and e.entity_id != entity_id:
                continue
            if names is not None and e.event not in names:
                continue
            if target_entity_type is not UNSET and e.target_entity_type != target_entity_type:
                continue
            if target_entity_id is not UNSET and e.target_entity_id != target_entity_id:
                continue
            out.append((t_us, off, e))
        out.sort(key=lambda h: (h[0], h[1]), reverse=reversed)
        if limit is not None and limit >= 0:
            out = out[:limit]
        for _, _, e in out:
            yield e

    def find_by_entities(
        self,
        app_id: int,
        entity_type: str,
        entity_ids: "Sequence[str]",
        channel_id: Optional[int] = None,
        start_time: Optional[_dt.datetime] = None,
        until_time: Optional[_dt.datetime] = None,
        event_names: "Optional[Sequence[str]]" = None,
        target_entity_type: Any = UNSET,
        target_entity_id: Any = UNSET,
        limit_per_entity: Optional[int] = None,
        reversed: bool = False,
    ) -> dict[str, list[Event]]:
        """ONE log scan for the whole entity batch — the contract default
        would rescan (and native-scan-sort) the log once per entity. The
        scan filters on everything but entity_id (the scanner has no set
        predicate); membership is applied while grouping, in the same
        (time, offset) order a per-entity ``find`` yields, so per-entity
        results match the per-entity read exactly."""
        ids = list(dict.fromkeys(entity_ids))
        if not ids:
            return {}
        wanted = set(ids)
        events = (e for e in self.find(
            app_id, channel_id, start_time, until_time, entity_type, None,
            event_names, target_entity_type, target_entity_id,
            None, reversed=reversed,
        ) if e.entity_id in wanted)
        return self.group_events_by_entity(events, ids, limit_per_entity)

    def assemble_triples(
        self,
        app_id: int,
        channel_id: Optional[int] = None,
        start_time: Optional[_dt.datetime] = None,
        until_time: Optional[_dt.datetime] = None,
        entity_type: Optional[str] = None,
        event_names: Optional[Sequence[str]] = None,
        target_entity_type: Any = UNSET,
        value_property: Optional[str] = None,
        default_values: Optional[dict] = None,
        missing_value: float = 0.0,
        dedup: bool = False,
        n_shards: Optional[int] = None,
        shard_index: int = 0,
        chunk_rows: int = 262_144,
    ):
        log = self._log(app_id, channel_id)
        flt = make_filter(
            start_time, until_time, entity_type, None, event_names,
            _UNSET_MAP(target_entity_type),
        )
        with log.lock:
            log.refresh()
            # sharding happens inside the C++ scan (crc32 entity partition),
            # so a multi-process job's per-process read materializes ~1/P of
            # the store — never a full replica
            result = native_assemble(
                log.path, flt, value_property, default_values,
                missing_value, dedup, n_shards=n_shards,
                shard_index=shard_index,
            )
        # host side, so either reader is correct — but the operator (and
        # chip_smoke.py) should see which one served a multi-million-row read
        logger.info("assemble_triples: app %s read by the %s", app_id,
                    "Python mirror (native scan unavailable)"
                    if result is None else "native C++ scan")
        if result is None:
            return super().assemble_triples(
                app_id, channel_id, start_time, until_time, entity_type,
                event_names, target_entity_type, value_property,
                default_values, missing_value, dedup,
                n_shards=n_shards, shard_index=shard_index,
                chunk_rows=chunk_rows,
            )
        return result

    def aggregate_properties(
        self,
        app_id: int,
        entity_type: str,
        channel_id: Optional[int] = None,
        start_time: Optional[_dt.datetime] = None,
        until_time: Optional[_dt.datetime] = None,
        required: Optional[Sequence[str]] = None,
        n_shards: Optional[int] = None,
        shard_index: int = 0,
    ) -> dict[str, PropertyMap]:
        if n_shards is not None:
            # sharded fold stays in Python (per-entity snapshots are exact
            # per shard); the native fold currently folds the whole log
            return super().aggregate_properties(
                app_id, entity_type, channel_id, start_time, until_time,
                required, n_shards, shard_index,
            )
        log = self._log(app_id, channel_id)
        flt = make_filter(
            start_time, until_time, entity_type, None, None,
        )
        with log.lock:
            log.refresh()
            buf = native_fold(log.path, flt)
        if buf is None:
            return super().aggregate_properties(
                app_id, entity_type, channel_id, start_time, until_time, required
            )
        agg = _decode_fold(buf)
        if required:
            req = set(required)
            agg = {k: v for k, v in agg.items() if req <= set(v.keys())}
        return agg


def _UNSET_MAP(v: Any) -> Any:
    """Translate storage-layer UNSET to the native layer's sentinel."""
    from incubator_predictionio_tpu.native import _UNSET as NATIVE_UNSET

    return NATIVE_UNSET if v is UNSET else v


def _decode_fold(buf: bytes) -> dict[str, PropertyMap]:
    import struct

    (n,) = struct.unpack_from("<I", buf, 0)
    pos = 4
    out: dict[str, PropertyMap] = {}
    for _ in range(n):
        (klen,) = struct.unpack_from("<H", buf, pos)
        pos += 2
        entity_id = buf[pos:pos + klen].decode()
        pos += klen
        first_us, last_us = struct.unpack_from("<qq", buf, pos)
        pos += 16
        props, pos = fmt.decode_tlv(buf, pos)
        out[entity_id] = PropertyMap(
            props,
            fmt._from_us_tz(first_us, 0),
            fmt._from_us_tz(last_us, 0),
        )
    return out


@register_backend("eventlog")
class EventLogStorageClient(StorageClient):
    """EVENTDATA-only backend over native append-only logs."""

    def __init__(self, config: dict[str, str]):
        super().__init__(config)
        path = config.get("PATH")
        if not path:
            base = os.environ.get("PIO_FS_BASEDIR", os.path.expanduser("~/.pio_store"))
            path = os.path.join(base, "eventlog")
        # READ_ONLY=1: replication-follower mode (serve reads beside the
        # replicated appends without ever taking a writer flock)
        self._events = EventLogEvents(
            path, read_only=str(config.get("READ_ONLY", "")).lower()
            in ("1", "true", "yes"))

    def events(self) -> EventStore:
        return self._events

    def close(self) -> None:
        self._events.close()
