"""Each WAL driver's control flow at a tiny size on the CPU, called
directly: set-up, window, metrics, and a comparison that passes."""

import pytest

from bench_tiny import run, wal_cell


@pytest.mark.parametrize("name", ["wal-large.ingest16", "wal-large.sync1",
                                  "wal-large.recover"])
def test_wal_cell_runs_and_is_correct(name, tmp_path):
    out = run(wal_cell(name), 0.5, tmp_path)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    m = out["metrics"]
    assert m["setup_s"]["value"] > 0
    if name == "wal-large.recover":
        assert m["recover_s"]["value"] > 0
    else:
        assert m["durable_MBps"]["value"] > 0
        assert m["ack_p95_ms"]["value"] > 0
    assert list(out)[-1] == "checks"
