"""A WAL run with its timed path broken underneath comes out not
correct, and so do the controls (a backup dropped from replication:
the three-copy guarantee broken; a write quorum of 1: the ack
guarantee broken), and a recovery that skips payload validation."""

import pytest

from bench_tiny import run, wal_cell


def _copy_batch_fault(kind):
    from repro.core.log import Log
    orig = Log.copy_batch

    def broken(self, batch, payloads):
        payloads = list(payloads)
        if kind == "unchanged":          # acked, never written
            return 0.0
        if kind == "half_batch":         # half of each wave left out
            payloads = payloads[:len(payloads) // 2] + [
                bytes(len(p)) for p in payloads[len(payloads) // 2:]]
            if len(batch.lsns) == 1:
                payloads = [bytes(len(payloads[0]))]
        if kind == "altered":            # a byte changed where produced
            payloads = [bytes([p[0] ^ 1]) + bytes(p[1:]) for p in payloads]
        return orig(self, batch, payloads)
    return broken


@pytest.mark.parametrize("kind", ["unchanged", "half_batch", "altered"])
def test_payload_faults_are_not_correct(kind, monkeypatch, tmp_path):
    from repro.core.log import Log
    monkeypatch.setattr(Log, "copy_batch", _copy_batch_fault(kind))
    out = run(wal_cell("wal-large.ingest16"), 0.5, tmp_path)
    assert not out["correct"]
    assert out["checks"]["records_missing_or_differ"]["value"] > 0


def test_hash_altered_where_produced_is_not_correct(monkeypatch, tmp_path):
    import repro.core.log as L
    orig = L._rec_checksum
    monkeypatch.setattr(L, "_rec_checksum",
                        lambda *a: (orig(*a) + 1) & 0xFFFFFFFF)
    out = run(wal_cell("wal-large.sync1"), 0.5, tmp_path)
    assert not out["correct"]
    assert out["checks"]["hash_not_plain"]["value"] > 0


def test_control_backup_dropped_is_not_correct(monkeypatch, tmp_path):
    from bench.lib import wal
    from bench.tools.control_wal import backup_dropped
    monkeypatch.setattr(wal, "build", backup_dropped(wal.build))
    out = run(wal_cell("wal-large.ingest16"), 0.5, tmp_path)
    assert not out["correct"]
    assert out["checks"]["backup_records_differ"]["value"] > 0


@pytest.mark.parametrize("name", ["wal-large.ingest16", "wal-large.sync1"])
def test_control_quorum1_is_not_correct(name, monkeypatch, tmp_path):
    """Acked once the primary alone holds a record: the backups catch up
    before the window closes, but not before the acks."""
    from bench.lib import wal
    from bench.tools.control_wal import quorum1
    monkeypatch.setattr(wal, "build", quorum1(wal.build))
    out = run(wal_cell(name), 0.5, tmp_path)
    assert not out["correct"]
    assert out["checks"]["acked_before_quorum"]["value"] > 0
    assert out["checks"]["backup_records_differ"]["value"] == 0


def test_recovery_without_payload_validation_is_not_correct(
        monkeypatch, tmp_path):
    import repro.core.log as L

    def accept_all(raw, items):
        list(items)
        return None
    monkeypatch.setattr(L, "_first_bad_payload", accept_all)
    out = run(wal_cell("wal-large.recover"), 0.5, tmp_path)
    assert not out["correct"]
    c = out["checks"]
    assert c["opens_ending_elsewhere"]["value"] > 0
    assert c["records_missing_or_differ"]["value"] > 0
