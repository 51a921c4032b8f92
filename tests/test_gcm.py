"""AES-128-GCM: NIST CAVP vectors, backend differentials, the O(1)-launch
ledger, and the constant-time audit of the fused seal program.

Oracle: an independent pure-python GCM built on big-endian field ints
(the FIPS bit order — deliberately the OPPOSITE convention from the
engine's reflected little-endian limbs, so a convention bug cannot
cancel out), anchored below against the canonical AES-128-GCM test
cases 1–4 (McGrew-Viega / NIST CAVP set: zero-key empty, zero-key
one-block, 4-block, and AAD + truncated-plaintext)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs as _obs
from repro.core import plan_program as pp
from repro.core import telemetry
from repro.core.static_registry import FixedLatencyError
from repro.crypto import aes as aes_mod
from repro.crypto import gcm
from repro.crypto.registry import REGISTRY

ALL_BACKENDS = ("einsum", "reference", "kernel", "sparse")


# ---------------------------------------------------------------------------
# Independent reference (big-endian field convention)
# ---------------------------------------------------------------------------

def _gmul(x: int, y: int) -> int:
    R = 0xE1000000000000000000000000000000
    z, v = 0, x
    for i in range(127, -1, -1):
        if (y >> i) & 1:
            z ^= v
        v = (v >> 1) ^ (R if v & 1 else 0)
    return z


def _ghash_ref(h: bytes, data: bytes) -> bytes:
    hi = int.from_bytes(h, "big")
    y = 0
    for i in range(0, len(data), 16):
        y = _gmul(hi, y ^ int.from_bytes(data[i:i + 16], "big"))
    return y.to_bytes(16, "big")


def _aes_ref(key: bytes, block: bytes) -> bytes:
    return gcm._host_encrypt_block(aes_mod.key_expansion(key), block)


def gcm_ref(key: bytes, iv: bytes, pt: bytes, aad: bytes = b""):
    assert len(iv) == 12
    h = _aes_ref(key, b"\x00" * 16)
    ct = b""
    for t in range(-(-len(pt) // 16)):
        ks = _aes_ref(key, iv + (t + 2).to_bytes(4, "big"))
        ct += bytes(a ^ b for a, b in zip(pt[16 * t:16 * t + 16], ks))
    pad = lambda x: x + b"\x00" * ((-len(x)) % 16)
    lens = ((8 * len(aad)).to_bytes(8, "big")
            + (8 * len(pt)).to_bytes(8, "big"))
    s = _ghash_ref(h, pad(aad) + pad(ct) + lens)
    tag = bytes(a ^ b for a, b in
                zip(s, _aes_ref(key, iv + b"\x00\x00\x00\x01")))
    return ct, tag


# The canonical AES-128-GCM vectors (all 96-bit IV):
#   case 1: empty everything; case 2: one zero block;
#   case 3: 4 full blocks, no AAD; case 4: AAD + 60-byte plaintext
#   (non-multiple-of-16).
_K34 = bytes.fromhex("feffe9928665731c6d6a8f9467308308")
_IV34 = bytes.fromhex("cafebabefacedbaddecaf888")
_PT3 = bytes.fromhex(
    "d9313225f88406e5a55909c5aff5269a86a7a9531534f7da2e4c303d8a318a72"
    "1c3c0c95956809532fcf0e2449a6b525b16aedf5aa0de657ba637b391aafd255")
_CT3 = bytes.fromhex(
    "42831ec2217774244b7221b784d0d49ce3aa212f2c02a4e035c17e2329aca12e"
    "21d514b25466931c7d8f6a5aac84aa051ba30b396a0aac973d58e091473f5985")
_AAD4 = bytes.fromhex("feedfacedeadbeeffeedfacedeadbeefabaddad2")

CAVP = [
    # (key, iv, pt, aad, ct, tag)
    (b"\x00" * 16, b"\x00" * 12, b"", b"", b"",
     bytes.fromhex("58e2fccefa7e3061367f1d57a4e7455a")),
    (b"\x00" * 16, b"\x00" * 12, b"\x00" * 16, b"",
     bytes.fromhex("0388dace60b6a392f328c2b971b2fe78"),
     bytes.fromhex("ab6e47d42cec13bdf53a67b21257bddf")),
    (_K34, _IV34, _PT3, b"", _CT3,
     bytes.fromhex("4d5c2af327cd64a62cf35abd2ba6fab4")),
    (_K34, _IV34, _PT3[:60], _AAD4, _CT3[:60],
     bytes.fromhex("5bc94fbc3221a5db94fae95ae7121a47")),
]

# Geometry sweep: empty, empty-AAD, AAD-only, multi-block, partial final
# block, AAD partial block.
GEOMETRIES = [(0, 0), (16, 0), (0, 20), (48, 16), (53, 0), (40, 13)]

KEY = bytes(range(16))


def _vecs(pt_len, aad_len, b=3):
    pts = [bytes((i * 11 + r * 5 + 1) & 0xFF for i in range(pt_len))
           for r in range(b)]
    aads = [bytes((i * 3 + r) & 0xFF for i in range(aad_len))
            for r in range(b)]
    ivs = [bytes((r + i) & 0xFF for i in range(12)) for r in range(b)]
    return ivs, pts, aads


class TestReferenceAnchors:
    def test_reference_matches_cavp(self):
        for key, iv, pt, aad, ct, tag in CAVP:
            got_ct, got_tag = gcm_ref(key, iv, pt, aad)
            assert got_ct == ct and got_tag == tag

    def test_host_aes_fips197(self):
        c = _aes_ref(bytes.fromhex("000102030405060708090a0b0c0d0e0f"),
                     bytes.fromhex("00112233445566778899aabbccddeeff"))
        assert c == bytes.fromhex("69c4e0d86a7b0430d8cdb78070b4c55a")


class TestGhashPrimitive:
    @pytest.mark.parametrize("backend", ALL_BACKENDS)
    @pytest.mark.parametrize("mode", ["powers", "horner"])
    def test_ghash_matches_reference(self, backend, mode):
        h_blk = _aes_ref(KEY, b"\x00" * 16)
        h = gcm._hash_key(KEY)
        data = bytes((i * 7 + 5) & 0xFF for i in range(64))
        got = gcm.ghash(h, data, mode=mode, backend=backend)
        assert got == _ghash_ref(h_blk, data)

    def test_powers_is_one_pass(self):
        from repro.core import crossbar as xb
        h = gcm._hash_key(KEY)
        data = bytes(96)
        t0 = xb.apply_call_count()
        gcm.ghash(h, data, mode="powers", backend="einsum")
        one = xb.apply_call_count() - t0
        t0 = xb.apply_call_count()
        gcm.ghash(h, data, mode="horner", backend="einsum")
        per_block = xb.apply_call_count() - t0
        assert one == 1
        assert per_block == len(data) // 16

    def test_clmul_reduction_matches_field_oracle(self):
        """The fused program's multiply by H: the carry-less product of
        the factor in coefficient order (``_coeff_row``) with H, reduced
        by ``_reduction``'s select lists, read back in block order."""
        h = gcm._hash_key(KEY)
        x = bytes(range(16))
        bits = np.unpackbits(np.frombuffer(x, np.uint8), bitorder="little")
        factor = sum(int(bits[gcm._coeff_row(c)]) << c for c in range(128))
        prod = 0
        for i in range(128):
            if (h >> i) & 1:
                prod ^= factor << i
        red = gcm._reduction()
        out = np.zeros(128, np.uint8)
        for r in range(128):
            out[r] = sum((prod >> k) & 1
                         for k in red[gcm._coeff_row(r)]) & 1
        got = np.packbits(out, bitorder="little").tobytes()
        assert got == _ghash_ref(_aes_ref(KEY, b"\x00" * 16), x)


class TestCAVPAllBackends:
    @pytest.mark.parametrize("backend", ALL_BACKENDS + ("fused",))
    def test_cavp_vectors(self, backend):
        for key, iv, pt, aad, ct, tag in CAVP:
            sealed = gcm.aes128_gcm_seal(key, iv, pt, aad,
                                         backend=backend)
            assert sealed == ct + tag, (backend, (ct + tag).hex(),
                                        sealed.hex())
            assert gcm.aes128_gcm_open(key, iv, sealed, aad,
                                       backend=backend) == pt


class TestFusedDifferential:
    @pytest.mark.parametrize("pt_len,aad_len", GEOMETRIES)
    def test_fused_batch_matches_reference(self, pt_len, aad_len):
        ivs, pts, aads = _vecs(pt_len, aad_len)
        sealed = gcm.aes128_gcm_seal_batch(KEY, ivs, pts, aads,
                                           backend="fused")
        for r, s in enumerate(sealed):
            ct, tag = gcm_ref(KEY, ivs[r], pts[r], aads[r])
            assert s == ct + tag, (pt_len, aad_len, r)
        assert gcm.aes128_gcm_open_batch(KEY, ivs, sealed, aads,
                                         backend="fused") == pts

    def test_tamper_raises_with_indices(self):
        ivs, pts, aads = _vecs(32, 8, b=4)
        sealed = gcm.aes128_gcm_seal_batch(KEY, ivs, pts, aads)
        bad = list(sealed)
        bad[1] = bad[1][:-1] + bytes([bad[1][-1] ^ 1])       # tag bit
        bad[3] = bytes([bad[3][0] ^ 0x80]) + bad[3][1:]      # ct bit
        with pytest.raises(gcm.InvalidTagError) as ei:
            gcm.aes128_gcm_open_batch(KEY, ivs, bad, aads)
        assert ei.value.indices == (1, 3)
        # AAD tamper on the chained path too
        with pytest.raises(gcm.InvalidTagError):
            gcm.aes128_gcm_open(KEY, ivs[0], sealed[0], b"not-the-aad",
                                backend="einsum")

    def test_geometry_validation(self):
        with pytest.raises(ValueError, match="96-bit IV"):
            gcm.aes128_gcm_seal(KEY, b"\x00" * 16, b"hi")
        with pytest.raises(ValueError, match="geometry"):
            gcm.aes128_gcm_seal_batch(
                KEY, [b"\x00" * 12] * 2, [b"a", b"bb"])


KEYS8 = [bytes((31 * r + 7 * i + 3) & 0xFF for i in range(16))
         for r in range(8)]


class TestPerLaneKeys:
    """One program per geometry, each lane under its own key: the key
    material is the launch's key operand, not part of the program."""

    def test_key_material_matches_per_key_expansion(self):
        keys = KEYS8 + [b"\x00" * 16, _K34]
        km = gcm.key_material(keys)
        assert km.shape == (len(keys), gcm.KEY_BYTES)
        for row, key in zip(km, keys):
            rks = aes_mod.key_expansion(key).astype(np.uint8).reshape(-1)
            assert row[:176].tobytes() == rks.tobytes()
            assert row[176:].tobytes() == gcm._hash_key(key).to_bytes(
                16, "little")
        lanes = gcm.key_lanes(keys)
        idx = jnp.asarray([3, 0, 9, 3], jnp.int32)
        np.testing.assert_array_equal(
            np.asarray(gcm.session_keys(jnp.asarray(km), idx)),
            lanes[:, [3, 0, 9, 3]])

    @pytest.mark.parametrize("backend", ["fused", "einsum"])
    @pytest.mark.parametrize("pt_len,aad_len", [(17, 5), (48, 16)])
    def test_eight_records_under_eight_keys(self, backend, pt_len,
                                            aad_len):
        from cryptography.hazmat.primitives.ciphers.aead import AESGCM
        ivs, pts, aads = _vecs(pt_len, aad_len, b=8)
        sealed = gcm.aes128_gcm_seal_batch(KEYS8, ivs, pts, aads,
                                           backend=backend)
        for key, iv, pt, aad, got in zip(KEYS8, ivs, pts, aads, sealed):
            assert got == AESGCM(key).encrypt(iv, pt, aad)
        assert gcm.aes128_gcm_open_batch(KEYS8, ivs, sealed, aads,
                                         backend=backend) == pts
        with pytest.raises(gcm.InvalidTagError) as ei:
            gcm.aes128_gcm_open_batch(KEYS8[1:] + KEYS8[:1], ivs, sealed,
                                      aads, backend=backend)
        assert ei.value.indices == tuple(range(8))

    def test_two_keys_give_one_program_and_one_compile(self):
        """The program, its encoding, its dense slots and its registry
        fingerprint are those of the geometry, whatever the key, and a
        second key launches the executable the first one compiled."""
        ivs, pts, aads = _vecs(29, 7, b=2)
        prog_key, prog, _ = gcm.gcm_program(29, 7)
        assert prog_key == "gcm/aes128/seal/m2.13a1"
        seen = []
        misses = pp.program_cache_info()["misses"]
        for key in KEYS8[:2]:
            with telemetry.delta() as d:
                gcm.aes128_gcm_seal_batch(key, ivs, pts, aads,
                                          fixed_latency=True)
            built = gcm.build_gcm_program(29, 7)[0]
            assert gcm.gcm_program(29, 7)[1] is prog
            seen.append((pp.encode_steps(built).tobytes(),
                         pp.dense_slots(built),
                         [np.asarray(p.idx).tobytes() for p in built.plans],
                         REGISTRY.program_fingerprint(prog_key),
                         d()["megakernel_entries_walked"]))
        assert pp.program_cache_info()["misses"] - misses == 1
        assert seen[0] == seen[1]


class TestLaunchLedger:
    def test_batch_seal_is_one_launch(self):
        """B=32 multi-block records: the whole batch seals in ONE
        program launch, with the avoided chained passes ledgered."""
        ivs, pts, aads = _vecs(48, 16, b=32)
        gcm.gcm_program(48, 16)                 # warm the program cache
        from repro.core import crossbar as xb
        l0 = pp.program_launch_count()
        a0 = xb.apply_call_count()
        p0 = pp.passes_avoided_count()
        sealed = gcm.aes128_gcm_seal_batch(KEY, ivs, pts, aads,
                                           backend="fused",
                                           fixed_latency=True)
        assert pp.program_launch_count() - l0 == 1
        assert xb.apply_call_count() - a0 == 0
        assert pp.passes_avoided_count() > p0
        ct, tag = gcm_ref(KEY, ivs[7], pts[7], aads[7])
        assert sealed[7] == ct + tag

    def test_fixed_latency_fused_contract(self):
        ivs, pts, aads = _vecs(32, 0, b=4)
        # Twice through the observed region: the registry fingerprints
        # the schedule on the first call and asserts invariance after.
        for _ in range(2):
            gcm.aes128_gcm_seal_batch(KEY, ivs, pts, aads,
                                      backend="fused",
                                      fixed_latency=True)

    def test_seal_telemetry_counters(self):
        ivs, pts, aads = _vecs(16, 0, b=2)
        c0 = telemetry.counter("gcm_seal_calls")
        r0 = telemetry.counter("gcm_seal_records")
        gcm.aes128_gcm_seal_batch(KEY, ivs, pts, aads, backend="fused")
        assert telemetry.counter("gcm_seal_calls") == c0 + 1
        assert telemetry.counter("gcm_seal_records") == r0 + 2

    def test_obs_histogram_and_gauge(self):
        """The seal launch's latency reaches the metrics through the
        ``gcm_seal`` span while recording is on; the lift-cache gauge
        is there either way."""
        ivs, pts, aads = _vecs(40, 0, b=2)
        before = _obs.snapshot()["histograms"].get("gcm_seal", {})
        was = _obs.enabled()
        _obs.enable()
        try:
            gcm.aes128_gcm_seal_batch(KEY, ivs, pts, aads, backend="fused")
        finally:
            (_obs.enable if was else _obs.disable)()
        snap = _obs.snapshot()
        hists = snap.get("histograms", snap)
        assert hists["gcm_seal"]["count"] == before.get("count", 0) + 1
        gauges = snap.get("gauges", {})
        assert "ghash_lift_cache" in gauges


class TestConstantTime:
    def test_audit_full_seal_program(self):
        """The complete fused seal — every AES round, the counter
        constants, GHASH absorb, and the tag — abstract-evaluates with
        payload tracers: no value-dependent host sync anywhere."""
        fn, lay = gcm.seal_device_fn(53, 18)
        out = REGISTRY.audit_constant_time(
            "gcm_seal_audit", fn, jnp.zeros((lay["n"], 8), jnp.int32),
            jnp.zeros((gcm.KEY_ROWS, 8), jnp.int32))
        assert out.shape == (lay["n"], 8)

    def test_audit_open_program(self):
        fn, lay = gcm.seal_device_fn(32, 0, open_mode=True)
        REGISTRY.audit_constant_time(
            "gcm_open_audit", fn, jnp.zeros((lay["n"], 2), jnp.int32),
            jnp.zeros((gcm.KEY_ROWS, 2), jnp.int32))

    def test_program_passes_property(self):
        """The program's pass ledger is geometry-determined: trips =
        m+1 blocks, each a full AES-128 (4 permutes/round) plus the
        absorb pipeline — payload never changes it."""
        _, prog, _ = gcm.gcm_program(48, 16)
        _, prog2, _ = gcm.gcm_program(48, 16)
        assert prog is prog2                    # registry-cached
        assert prog.rounds == 1
        assert prog.passes == sum(
            1 for s in prog.steps if s.op == pp.PERMUTE)
