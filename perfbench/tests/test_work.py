import pytest

from perfbench import spec

sha3 = spec.load_module(spec.HERE + "/work/sha3_256.py")
gcm = spec.load_module(spec.HERE + "/work/gcm_seal.py")


@pytest.mark.parametrize("n, blocks", [
    (0, 1), (16, 1), (64, 1), (134, 1), (135, 1), (136, 2), (256, 2),
    (1024, 8), (8192, 61), (16384, 121)])
def test_sha3_blocks_and_bytes_by_hand(n, blocks):
    # pad10*1 adds the domain byte and the final bit: a message of
    # n bytes fills ceil((n + 1) / 136) blocks; each Keccak-f reads and
    # writes the 200-byte state.
    assert sha3.blocks(n) == blocks
    assert sha3.request_bytes(message_bytes=n) == blocks * 400


@pytest.mark.parametrize("pt, aad, moved", [
    (17, 5, 12 + 5 + 17 + 17 + 16),
    (65, 5, 12 + 5 + 65 + 65 + 16),
    (1025, 5, 12 + 5 + 1025 + 1025 + 16),
    (0, 0, 28)])
def test_gcm_bytes_by_hand(pt, aad, moved):
    assert gcm.request_bytes(pt_len=pt, aad_len=aad) == moved


def test_peaks_by_device_kind():
    import json

    peaks = json.load(open(spec.HERE + "/peaks.json"))
    v5e = spec.peak(peaks, "TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["int8_ops_per_s"] == 393e12
    assert "Google Cloud" in v5e["source"]
    with pytest.raises(KeyError):
        spec.peak(peaks, "cpu")
