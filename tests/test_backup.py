"""Disaster recovery (docs/dr.md): consistent point-in-time backup,
verified restore, incremental chains, and the staleness health row.

Everything here is tier-1: in-process, tmpdir stores, zero wall sleeps.
The process-boundary version (SIGKILL the event server mid-ingest, rm -rf
its data dir, restore, restart, ack parity by id set) lives in
tests/test_chaos_procs.py; the measured RPO/RTO drill is
``python drills.py --config disaster_recovery``.
"""

import datetime as dt
import json
import os
import pickle
import shutil

import numpy as np
import pytest

from incubator_predictionio_tpu.backup import (
    BackupError,
    BackupSet,
    BackupSource,
    RestoreTargets,
    create_backup,
    read_verify,
    restore_backup,
    verify_backup,
)
from incubator_predictionio_tpu.backup.manifest import prune
from incubator_predictionio_tpu.data import DataMap, Event
from incubator_predictionio_tpu.data.storage import Storage
from incubator_predictionio_tpu.data.storage.base import (
    AccessKey,
    App,
    Channel,
    EngineInstance,
    JobRecord,
    Model,
)
from incubator_predictionio_tpu.native import format as fmt
from incubator_predictionio_tpu.resilience.wal import SpillWal
from incubator_predictionio_tpu.streaming import delta as deltas
from incubator_predictionio_tpu.streaming import feed as feeds

UTC = dt.timezone.utc


def t(n):
    return dt.datetime(2024, 1, 1, 0, 0, n % 60, tzinfo=UTC)


def mk_event(i):
    return Event(event="rate", entity_type="user", entity_id=f"u{i}",
                 target_entity_type="item", target_entity_id=f"i{i % 5}",
                 properties=DataMap({"rating": float(1 + i % 5)}),
                 event_time=t(i))


def storage_env(tmp_path, name="live"):
    return {
        "PIO_STORAGE_SOURCES_EL_TYPE": "eventlog",
        "PIO_STORAGE_SOURCES_EL_PATH": str(tmp_path / f"{name}-elog"),
        "PIO_STORAGE_SOURCES_SQ_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_SQ_PATH": str(tmp_path / f"{name}-meta.db"),
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "EL",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "SQ",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "SQ",
    }


@pytest.fixture()
def host(tmp_path):
    """One live 'host': eventlog EVENTDATA + sqlite METADATA/MODELDATA,
    a spill WAL with a committed and a pending record, and streaming
    state (cursor + archived delta + trainer state)."""
    st = Storage(storage_env(tmp_path))
    apps = st.get_meta_data_apps()
    app_id = apps.insert(App(0, "drapp", "dr fixture"))
    st.get_meta_data_access_keys().insert(AccessKey("dr-key", app_id, ()))
    st.get_meta_data_channels().insert(Channel(0, "live", app_id))
    ei = st.get_meta_data_engine_instances()
    inst_id = ei.insert(EngineInstance(
        id="", status="COMPLETED", start_time=t(0), end_time=t(1),
        engine_id="eng", engine_version="1", engine_variant="default",
        engine_factory="pkg.Factory"))
    st.get_model_data_models().insert(Model(inst_id, b"\x01model" * 64))
    jobs = st.get_meta_data_jobs()
    job_id = jobs.insert(JobRecord(id="", kind="train", status="COMPLETED",
                                   submitted_at=t(2)))
    # advance the CAS version twice: the restored record must carry it
    j = jobs.get(job_id)
    assert jobs.cas(j, 0) and jobs.cas(jobs.get(job_id), 1)

    events = st.get_events()
    events.init(app_id)
    acked = events.insert_batch([mk_event(i) for i in range(30)], app_id)

    wal_dir = tmp_path / "wal"
    wal = SpillWal(str(wal_dir))
    committed_seq = wal.append(
        [{"event": mk_event(101).to_json_dict(), "app_id": app_id}])
    # the commit cursor is a watermark: commit the first record, then
    # append a second that stays PENDING — the unflushed tail the
    # restore's WAL replay recovers
    wal.commit(committed_seq)
    wal.append([{"event": mk_event(100).to_json_dict(), "app_id": app_id}])
    wal.close()

    stream_dir = tmp_path / "stream"
    log_path = events.log_path(app_id)
    log_end = fmt.valid_extent(open(log_path, "rb").read())
    feeds.write_cursor(str(stream_dir), {
        "seq": log_end, "chain_base": len(fmt.MAGIC),
        "delta_head": log_end, "base_instance": inst_id})
    deltas.save_delta(str(stream_dir), deltas.ModelDelta(
        base_instance=inst_id, chain_base=len(fmt.MAGIC),
        from_seq=len(fmt.MAGIC), to_seq=log_end,
        user_rows={0: np.ones(9, np.float32)}, item_rows={}))
    with open(stream_dir / "trainer.pkl", "wb") as f:
        pickle.dump({"to_seq": log_end, "chain_base": len(fmt.MAGIC),
                     "delta_head": log_end, "trainer": {}}, f)

    host = {
        "storage": st, "tmp": tmp_path, "app_id": app_id,
        "acked": acked, "inst_id": inst_id, "job_id": job_id,
        "eventlog_dir": str(tmp_path / "live-elog"),
        "wal_dir": str(wal_dir), "stream_dir": str(stream_dir),
        "log_path": log_path, "log_end": log_end,
    }
    yield host
    host["storage"].close()  # tests may have swapped the storage in place


def make_source(host):
    return BackupSource(eventlog_dir=host["eventlog_dir"],
                        wal_dir=host["wal_dir"],
                        stream_state_dir=host["stream_dir"],
                        storage=host["storage"])


def restore_host(tmp_path, name="restored"):
    """Fresh target dirs + a fresh storage backend to load metadata into."""
    st = Storage(storage_env(tmp_path, name))
    targets = RestoreTargets(
        eventlog_dir=str(tmp_path / f"{name}-elog"),
        wal_dir=str(tmp_path / f"{name}-wal"),
        stream_state_dir=str(tmp_path / f"{name}-stream"))
    return st, targets


class TestCreateVerifyRestore:
    def test_smoke_round_trip(self, host, tmp_path):
        rep = create_backup(str(tmp_path / "bk"), make_source(host))
        assert rep["verify"]["clean"], rep["verify"]["errors"]
        assert rep["cuts"]["eventlog/app_1.piolog"] == host["log_end"]

        st2, targets = restore_host(tmp_path)
        rr = restore_backup(str(tmp_path / "bk"), targets, storage=st2,
                            replay_wal=True)
        # byte-identical files up to the cut
        orig = open(host["log_path"], "rb").read()[:host["log_end"]]
        log2 = open(os.path.join(targets.eventlog_dir,
                                 "app_1.piolog"), "rb").read()
        assert log2[:host["log_end"]] == orig
        # every acked event readable from the restored store, exactly once
        got = [e.event_id for e in st2.get_events().find(host["app_id"])]
        assert set(host["acked"]) <= set(got)
        assert len(got) == len(set(got))
        # the WAL's PENDING record replayed; the committed one did not dup
        assert rr["walReplayed"] == 1
        ents = [e.entity_id for e in st2.get_events().find(host["app_id"])]
        assert "u100" in ents and ents.count("u100") == 1
        # metadata byte-equivalent through the dump/load contract
        j = st2.get_meta_data_jobs().get(host["job_id"])
        assert j.version == 2
        assert not st2.get_meta_data_jobs().cas(j, 0)  # stale CAS fenced
        assert st2.get_meta_data_jobs().cas(j, 2)
        assert st2.get_model_data_models().get(
            host["inst_id"]).models == b"\x01model" * 64
        assert st2.get_meta_data_apps().get_by_name("drapp") is not None
        st2.close()

    def test_cut_excludes_live_writers_partial_record(self, host, tmp_path):
        """A half-appended record (the live-writer race) is cut away, not
        copied: the backup's log must end ON a record boundary."""
        with open(host["log_path"], "ab") as f:
            f.write(b"\x40\x00\x00\x00\x02partial")  # torn: length 64, 8 bytes
        rep = create_backup(str(tmp_path / "bk"), make_source(host))
        assert rep["cuts"]["eventlog/app_1.piolog"] == host["log_end"]
        assert rep["verify"]["clean"], rep["verify"]["errors"]
        bset = BackupSet(str(tmp_path / "bk"))
        data = bset.read_file(bset.tip(), "eventlog/app_1.piolog")
        assert fmt.valid_extent(data) == len(data)

    def test_restore_refuses_nonempty_target_unless_forced(
            self, host, tmp_path):
        create_backup(str(tmp_path / "bk"), make_source(host))
        tgt = tmp_path / "occupied"
        tgt.mkdir()
        (tgt / "survivor.piolog").write_bytes(b"PIOLOG01")
        with pytest.raises(BackupError, match="not empty"):
            restore_backup(str(tmp_path / "bk"),
                           RestoreTargets(eventlog_dir=str(tgt)))
        rr = restore_backup(str(tmp_path / "bk"),
                            RestoreTargets(eventlog_dir=str(tgt)),
                            force=True)
        assert rr["filesRestored"] >= 1

    def test_restore_verifies_while_writing(self, host, tmp_path):
        """A damaged entry aborts the restore mid-write instead of
        handing the host a log the manifest never promised."""
        rep = create_backup(str(tmp_path / "bk"), make_source(host))
        bset = BackupSet(str(tmp_path / "bk"))
        data_file = bset.tip().data_path("eventlog/app_1.piolog")
        blob = bytearray(open(data_file, "rb").read())
        blob[len(blob) // 2] ^= 0xFF
        open(data_file, "wb").write(bytes(blob))
        st2, targets = restore_host(tmp_path)
        with pytest.raises(BackupError, match="did not verify"):
            restore_backup(str(tmp_path / "bk"), targets, storage=st2)
        st2.close()
        assert rep["verify"]["clean"]  # the damage happened after create


class TestIncrementalChain:
    def test_incremental_copies_only_new_extent(self, host, tmp_path):
        bdir = str(tmp_path / "bk")
        create_backup(bdir, make_source(host))
        host["storage"].get_events().insert_batch(
            [mk_event(i) for i in range(30, 35)], host["app_id"])
        rep2 = create_backup(bdir, make_source(host))
        assert rep2["verify"]["clean"], rep2["verify"]["errors"]
        man = BackupSet(bdir).get(rep2["backupId"]).manifest
        fe = next(f for f in man["files"]
                  if f["path"] == "eventlog/app_1.piolog")
        assert fe["store"]["kind"] == "extent"
        assert fe["store"]["offset"] == host["log_end"]
        assert fe["storedBytes"] == fe["size"] - host["log_end"]
        # unchanged WAL segment references the parent, zero bytes stored
        wal_fe = next(f for f in man["files"]
                      if "/wal-" in f["path"])
        assert wal_fe["store"]["kind"] == "parent"
        assert wal_fe["storedBytes"] == 0
        # restoring the child materializes the FULL log through the chain
        st2, targets = restore_host(tmp_path)
        restore_backup(bdir, targets, storage=st2)
        got = list(st2.get_events().find(host["app_id"]))
        assert len(got) == 35
        st2.close()

    def test_rewritten_prefix_falls_back_to_full_copy(self, host, tmp_path):
        """Truncate-and-recreate between backups: the child must NOT
        compose two histories — prefix digest mismatch forces a full
        copy."""
        bdir = str(tmp_path / "bk")
        create_backup(bdir, make_source(host))
        host["storage"].close()
        os.remove(host["log_path"])
        st = Storage(storage_env(host["tmp"]))
        host["storage"] = st
        ev = st.get_events()
        ev.init(host["app_id"])
        ev.insert_batch([mk_event(i) for i in range(7)], host["app_id"])
        rep2 = create_backup(bdir, make_source(host))
        assert rep2["verify"]["clean"], rep2["verify"]["errors"]
        man = BackupSet(bdir).get(rep2["backupId"]).manifest
        fe = next(f for f in man["files"]
                  if f["path"] == "eventlog/app_1.piolog")
        assert fe["store"]["kind"] == "full"

    def test_prune_keeps_chain_ancestors(self, host, tmp_path):
        bdir = str(tmp_path / "bk")
        r1 = create_backup(bdir, make_source(host))
        host["storage"].get_events().insert_batch(
            [mk_event(40)], host["app_id"])
        r2 = create_backup(bdir, make_source(host))
        host["storage"].get_events().insert_batch(
            [mk_event(41)], host["app_id"])
        r3 = create_backup(bdir, make_source(host))
        removed = prune(bdir, keep=1)
        # r3 is incremental on r2 on r1: the whole chain survives keep=1
        assert removed == []
        assert {e.backup_id for e in BackupSet(bdir).entries()} == {
            r1["backupId"], r2["backupId"], r3["backupId"]}
        assert verify_backup(bdir, r3["backupId"])["clean"]
        # a later FULL backup makes the old chain prunable
        r4 = create_backup(bdir, make_source(host), incremental=False)
        removed = sorted(prune(bdir, keep=1))
        assert {e.backup_id for e in BackupSet(bdir).entries()} == {
            r4["backupId"]}
        assert len(removed) == 3

    def test_verify_detects_pruned_out_parent(self, host, tmp_path):
        bdir = str(tmp_path / "bk")
        r1 = create_backup(bdir, make_source(host))
        host["storage"].get_events().insert_batch(
            [mk_event(50)], host["app_id"])
        r2 = create_backup(bdir, make_source(host))
        shutil.rmtree(BackupSet(bdir).get(r1["backupId"]).path)
        report = verify_backup(bdir, r2["backupId"])
        assert not report["clean"]
        assert any("parent" in e for e in report["errors"])


class TestVerify:
    def test_detects_bitrot_with_position(self, host, tmp_path):
        bdir = str(tmp_path / "bk")
        rep = create_backup(bdir, make_source(host))
        bset = BackupSet(bdir)
        data_file = bset.tip().data_path("eventlog/app_1.piolog")
        blob = bytearray(open(data_file, "rb").read())
        blob[10] ^= 0x01
        open(data_file, "wb").write(bytes(blob))
        report = verify_backup(bdir, rep["backupId"])
        assert not report["clean"]
        assert any("app_1.piolog" in e and "CRC" in e
                   for e in report["errors"])
        # the verdict is durable: the entry's verify.json records it
        v = read_verify(bset.tip().path)
        assert v is not None and not v["clean"]


class TestRestoreSemantics:
    def test_cursor_clamped_and_ahead_state_dropped(self, host, tmp_path):
        """A cursor copied a moment after the log cut may point past it;
        the restore clamps it back so the suffix re-folds instead of being
        skipped — and trainer state/deltas past the cut go with it."""
        bdir = str(tmp_path / "bk")
        # poke the cursor (and trainer state + an archived delta) AHEAD
        # of the log end before the backup, simulating the copy race
        ahead = host["log_end"] + 1000
        feeds.write_cursor(host["stream_dir"], {
            "seq": ahead, "chain_base": len(fmt.MAGIC),
            "delta_head": ahead, "base_instance": host["inst_id"]})
        with open(os.path.join(host["stream_dir"], "trainer.pkl"),
                  "wb") as f:
            pickle.dump({"to_seq": ahead, "chain_base": len(fmt.MAGIC),
                         "delta_head": ahead, "trainer": {}}, f)
        deltas.save_delta(host["stream_dir"], deltas.ModelDelta(
            base_instance=host["inst_id"], chain_base=len(fmt.MAGIC),
            from_seq=host["log_end"], to_seq=ahead,
            user_rows={1: np.ones(9, np.float32)}, item_rows={}))
        create_backup(bdir, make_source(host))
        st2, targets = restore_host(tmp_path)
        rr = restore_backup(bdir, targets, storage=st2)
        st2.close()
        assert rr["cursorClamped"] is True
        assert rr["trainerStateDropped"] is True
        assert rr["deltasDropped"] == 1
        cur = feeds.read_cursor(targets.stream_state_dir)
        assert cur["seq"] == host["log_end"]
        assert cur["delta_head"] <= host["log_end"]
        assert not os.path.exists(
            os.path.join(targets.stream_state_dir, "trainer.pkl"))
        # the in-range archived delta survived
        kept = deltas.list_archived(targets.stream_state_dir)
        assert [(f, s) for f, s, _ in kept] == [
            (len(fmt.MAGIC), host["log_end"])]
        # and the restored feed accepts the clamped cursor (boundary walk)
        feeds.EventLogFeed(
            os.path.join(targets.eventlog_dir, "app_1.piolog"),
            from_seq=cur["seq"])

    def test_replication_epoch_bumped(self, host, tmp_path):
        """Restore fences stale peers exactly like a promote: the
        restored host comes up at epoch+1."""
        state = {"epoch": 3, "role": "primary", "fenced": False}
        with open(os.path.join(host["eventlog_dir"],
                               "repl-state.json"), "w") as f:
            json.dump(state, f)
        bdir = str(tmp_path / "bk")
        create_backup(bdir, make_source(host))
        st2, targets = restore_host(tmp_path)
        rr = restore_backup(bdir, targets, storage=st2)
        st2.close()
        assert rr["epoch"] == {"epochBefore": 3, "epochAfter": 4,
                               "bumped": True}
        with open(os.path.join(targets.eventlog_dir,
                               "repl-state.json")) as f:
            assert json.load(f)["epoch"] == 4

    def test_restore_into_different_metadata_backend(self, host, tmp_path):
        """The dump/load contract makes the metadata portable across
        backends: a sqlite-born backup restores into memory — and load
        REPLACES: survivor records in the target (channels included, the
        one DAO without get_all) do not outlive the restore."""
        bdir = str(tmp_path / "bk")
        create_backup(bdir, make_source(host))
        st2 = Storage({"PIO_STORAGE_SOURCES_M_TYPE": "memory"})
        st2.get_meta_data_apps().insert(App(host["app_id"], "drapp"))
        st2.get_meta_data_channels().insert(
            Channel(0, "survivor", host["app_id"]))
        restore_backup(
            bdir, RestoreTargets(eventlog_dir=str(tmp_path / "m-elog")),
            storage=st2)
        j = st2.get_meta_data_jobs().get(host["job_id"])
        assert j is not None and j.version == 2
        assert not st2.get_meta_data_jobs().cas(j, 1)
        assert st2.get_meta_data_apps().get_by_name("drapp") is not None
        names = [c.name for c in st2.get_meta_data_channels()
                 .get_by_app_id(host["app_id"])]
        assert names == ["live"]  # post-dump channel replaced, not merged
        st2.close()

    def test_small_segment_bytes_clamped_consistently(self, host,
                                                      tmp_path,
                                                      monkeypatch):
        """A sub-minimum PIO_BACKUP_SEGMENT_BYTES is clamped ONCE at
        create, so the manifest records the window size the digests used
        and verify agrees — a tiny knob value must not redden a perfectly
        good backup."""
        monkeypatch.setenv("PIO_BACKUP_SEGMENT_BYTES", "1024")
        rep = create_backup(str(tmp_path / "bk"), make_source(host))
        assert rep["verify"]["clean"], rep["verify"]["errors"]
        assert BackupSet(str(tmp_path / "bk")).tip().manifest[
            "segmentBytes"] == 4096
        assert verify_backup(str(tmp_path / "bk"))["clean"]

    def test_backup_reads_beside_live_writer_flock(self, host, tmp_path):
        """The create path is read-only: it runs while the single-writer
        store holds its flock (the backup-from-follower property — a
        follower's read-only view is the same file surface)."""
        events = host["storage"].get_events()
        log = events._log(host["app_id"], None)
        assert log.f is not None  # the writer flock is held RIGHT NOW
        rep = create_backup(str(tmp_path / "bk"), make_source(host))
        assert rep["verify"]["clean"]
        # and the writer is still writable afterwards
        events.insert(mk_event(60), host["app_id"])


class TestCliAndHealth:
    def test_cli_create_list_verify_restore(self, host, tmp_path,
                                            capsys):
        from incubator_predictionio_tpu.tools import cli

        bdir = str(tmp_path / "bk")
        args = ["--backup-dir", bdir,
                "--eventlog-dir", host["eventlog_dir"],
                "--wal-dir", host["wal_dir"],
                "--stream-state-dir", host["stream_dir"], "--no-meta"]
        assert cli.main(["backup", "create", *args]) == 0
        capsys.readouterr()
        assert cli.main(["backup", "list", "--backup-dir", bdir,
                         "--json"]) == 0
        rows = json.loads(capsys.readouterr().out)
        assert len(rows) == 1 and rows[0]["verified"]
        assert cli.main(["backup", "verify", "--backup-dir", bdir]) == 0
        assert cli.main([
            "backup", "restore", "--backup-dir", bdir,
            "--eventlog-dir", str(tmp_path / "cli-elog"), "--no-meta",
        ]) == 0
        restored = open(tmp_path / "cli-elog" / "app_1.piolog",
                        "rb").read()
        assert restored[:8] == fmt.MAGIC

    def test_health_backup_row(self, host, tmp_path):
        from incubator_predictionio_tpu.tools.cli import _backup_row

        bdir = str(tmp_path / "bk")
        # no backups at all → red
        row = _backup_row(bdir, max_age=None)
        assert row["red"] and row["status"] == "missing"
        old = dt.datetime(2024, 1, 1, tzinfo=UTC)
        create_backup(bdir, make_source(host), now=old)
        # fresh relative to `now` just after creation → green
        row = _backup_row(bdir, max_age=86400.0,
                          now=old.timestamp() + 3600)
        assert not row["red"] and row["status"] == "ok"
        # older than PIO_BACKUP_MAX_AGE → red (the stuck-cron alarm)
        row = _backup_row(bdir, max_age=86400.0,
                          now=old.timestamp() + 90000)
        assert row["red"] and row["status"] == "stale"
        # a failed verify on the newest entry → red regardless of age
        bset = BackupSet(bdir)
        data_file = bset.tip().data_path("eventlog/app_1.piolog")
        blob = bytearray(open(data_file, "rb").read())
        blob[12] ^= 0xFF
        open(data_file, "wb").write(bytes(blob))
        verify_backup(bdir)
        row = _backup_row(bdir, max_age=86400.0,
                          now=old.timestamp() + 3600)
        assert row["red"] and row["status"] == "verify-failed"

    def test_backup_metrics_counted(self, host, tmp_path):
        from incubator_predictionio_tpu.obs.metrics import (
            REGISTRY,
            parse_prometheus_text,
        )

        def snap():
            fams = parse_prometheus_text(REGISTRY.expose())
            return {name: sum(v for n, _, v in fam["samples"]
                              if not n.endswith(("_bucket", "_sum",
                                                 "_count")))
                    for name, fam in fams.items()
                    if name.startswith("pio_backup_")}

        before = snap()
        bdir = str(tmp_path / "bk")
        create_backup(bdir, make_source(host))
        st2, targets = restore_host(tmp_path)
        restore_backup(bdir, targets, storage=st2)
        st2.close()
        after = snap()
        assert after["pio_backup_created_total"] == \
            before.get("pio_backup_created_total", 0) + 1
        assert after["pio_backup_verified_total"] >= \
            before.get("pio_backup_verified_total", 0) + 1
        assert after["pio_backup_restores_total"] == \
            before.get("pio_backup_restores_total", 0) + 1
        assert after["pio_backup_bytes_copied_total"] > \
            before.get("pio_backup_bytes_copied_total", 0)
