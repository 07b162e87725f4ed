"""Operation and byte counts, peaks and the plain hash against hand
counts."""

import pytest

from bench_tiny import ROOT  # noqa: F401
from bench.lib import cost, peaks, phash

V5E = peaks.peaks_for("TPU v5 lite")


def test_hash_bytes_are_unpadded_seed_plus_payload():
    assert cost.hash_bytes([1, 5]) == (12 + 1) + (12 + 5)
    assert cost.hash_bytes([]) == 0


def test_ssd_forward_cost_hand_count():
    ops = [("f32", (1, 24, 2, 256, 64)), ("f32", (1, 24, 2, 256, 1)),
           ("f32", (1, 24, 2, 1, 256)), ("f32", (1, 1, 2, 256, 128)),
           ("f32", (1, 1, 2, 256, 128))]
    flops, nbytes = cost.ssd_forward_cost(ops)
    # C·Bᵀ, 2Q²N, per (batch, group, chunk): 2 of them; 2Q²P + 4QNP per
    # (batch, head, chunk): 48 of them
    assert flops == 2 * (2 * 256 * 256 * 128) + 48 * (
        2 * 256 * 256 * 64 + 4 * 256 * 128 * 64) == 838860800
    # xdt f32 + two decay layouts f32 + B, C f32 + y bf16 + state f32
    assert nbytes == (3145728 + 98304 + 524288 + 1572864 + 786432)


def test_mamba2_flops_per_token_hand_count():
    cfg = {"n_layer": 24, "d_model": 768, "vocab_size": 50280,
           "d_state": 128, "headdim": 64, "expand": 2, "d_conv": 4,
           "chunk_size": 256, "ngroups": 1}
    per_layer = (2 * 768 * 3352 + 2 * 4 * 1792 + 2 * 256 * 128
                 + 24 * (2 * 256 * 64 + 4 * 128 * 64) + 2 * 1536 * 768)
    assert per_layer == 9160704
    assert cost.mamba2_flops_per_token(cfg) == \
        3 * (24 * per_layer + 2 * 768 * 50280) == 891260928


def test_roofline_share_picks_the_binding_bound():
    share, bound = cost.roofline_share(1.97e12, 1e6, 0.1, V5E)
    assert bound == "compute" and share == pytest.approx(10.0)
    share, bound = cost.roofline_share(0.0, 8.19e8, 0.01, V5E)
    assert bound == "memory" and share == pytest.approx(10.0)


def test_peaks_table():
    assert V5E["bf16_flops"] == 197e12 and V5E["hbm_bytes_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")


def test_plain_hash_by_hand():
    r = phash.R
    h = phash.PlainHash(16)
    # lanes of (lsn=1, size=0): [1, 0, 0]
    assert h(1, b"") == 1
    # (lsn=1, size=1) || 0x02 -> lanes [1, 0, 1, 2]
    assert h(1, b"\x02") == (1 + 1 * r ** 2 + 2 * r ** 3) % 2 ** 32
    lsn = 2 ** 40 + 3
    want = (lsn & 0xFFFFFFFF) + (lsn >> 32) * r + 4 * r ** 2 \
        + 0x04030201 * r ** 3
    assert h(lsn, b"\x01\x02\x03\x04") == want % 2 ** 32
