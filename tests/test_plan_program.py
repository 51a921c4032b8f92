"""Plan programs and the VMEM-resident megakernel.

Differential contract: for ANY program, ``run_program(...,
backend="megakernel")`` (one Pallas launch, VM over resident registers)
equals ``backend="chained"`` (one ``apply_plan`` per PERMUTE step with
XLA elementwise between) — checked at every step count via program
prefixes, on the real Keccak/ChaCha programs and on synthetic programs
exercising every opcode.  Plus: telemetry (one launch, zero passes,
backend-split counters), registry/program fingerprints, fixed-latency
observation of the fused path, and the constant-time audit over a whole
program.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import crossbar as xb
from repro.core import plan_algebra as pa
from repro.core import plan_program as pp
from repro.core import telemetry
from repro.core.semiring import GF2, GF2_8
from repro.core.static_registry import FixedLatencyError, StaticPlanRegistry
from repro.crypto import chacha as cc
from repro.crypto import gcm
from repro.crypto import keccak as kk
from repro.crypto.registry import REGISTRY


def _bits(seed, shape):
    return jnp.asarray(
        np.random.default_rng(seed).integers(0, 2, shape), jnp.int32)


def _words(seed, shape):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, 1 << 32, shape, dtype=np.uint64).astype(np.uint32))


def _synthetic_program(n=16, n_regs=3):
    """A program touching every opcode (uint32 carrier)."""
    rng = np.random.default_rng(7)
    b = pp.ProgramBuilder("synthetic", n, n_regs=n_regs)
    route = xb.gather_plan(jnp.asarray(rng.permutation(n), np.int32), n)
    multi = xb.gather_plan(
        jnp.asarray(rng.integers(-3, n, (n, 4)), np.int32), n, semiring=GF2)
    b.permute(1, 0, route)
    b.add(0, 0, 1)
    b.permute(2, 0, multi)
    b.andn(1, 1, 2)
    b.xor(0, 0, 1)
    b.and_(2, 0, 1)
    b.add(0, 0, 2)
    b.rotlv(0, 0, rng.integers(0, 32, n))
    b.xor_const(0, 0, rng.integers(0, 1 << 16, n))
    return b.build()


class TestProgramIR:
    def test_scatter_plans_gather_normalised_by_builder(self):
        dest = jnp.asarray(np.random.default_rng(0).permutation(8), jnp.int32)
        scat = xb.scatter_plan(dest, 8)
        b = pp.ProgramBuilder("t", 8, n_regs=2)
        b.permute(0, 0, scat)
        prog = b.build()
        assert prog.plans[0].mode == xb.GATHER

    def test_rejects_geometry_mismatch(self):
        plan = pa.identity_plan(8)
        with pytest.raises(ValueError, match="state geometry"):
            pp.PlanProgram("bad", 16, (pp.Step(pp.PERMUTE, 0, 0, plan=0),),
                           (plan,), None, 2)

    def test_rejects_gf2_8_plans(self):
        idx = jnp.zeros((4, 1), jnp.int32)
        w = jnp.ones((4, 1), jnp.int32)
        plan = xb.gather_plan(idx, 4, weights=w, semiring=GF2_8)
        with pytest.raises(ValueError, match="REAL and GF2"):
            pp.PlanProgram("bad", 4, (pp.Step(pp.PERMUTE, 0, 0, plan=0),),
                           (plan,), None, 2)

    def test_rejects_bad_register(self):
        with pytest.raises(ValueError, match="register out of range"):
            pp.PlanProgram("bad", 4, (pp.Step(pp.XOR, 0, 0, b=5),), (), None,
                           2)

    def test_rejects_const_out_of_stride_range(self):
        b = pp.ProgramBuilder("t", 4, n_regs=2)
        base = b.add_const_rows(np.zeros((3, 4), np.int32))
        b.xor_const_at(0, 0, base)
        with pytest.raises(ValueError, match="out of range"):
            b.build(rounds=5, const_stride=1)  # rows 0..4 > 3 rows

    def test_rotlv_requires_unsigned(self):
        b = pp.ProgramBuilder("t", 4, n_regs=2)
        b.rotlv(0, 0, np.zeros(4, np.int32))
        prog = b.build()
        with pytest.raises(ValueError, match="unsigned"):
            pp.run_program(prog, jnp.zeros((4, 2), jnp.int32))

    def test_unroll_resolves_strided_consts(self):
        prog = kk.megakernel_program()
        flat = prog.unroll()
        assert flat.rounds == 1
        assert len(flat.steps) == prog.total_steps
        # round r's iota step references row r
        iota_steps = [s for s in flat.steps if s.op == pp.XOR_CONST]
        assert [s.const for s in iota_steps] == list(range(24))

    def test_passes_counts_permutes_times_rounds(self):
        assert kk.megakernel_program().passes == 24 * 3
        assert cc.megakernel_program().passes == 10 * 18


def _many_consts_program(n=16):
    """More constant rows than one 128-lane constants block holds, so
    the megakernel switches constant blocks mid-program."""
    rng = np.random.default_rng(11)
    b = pp.ProgramBuilder("many_consts", n, n_regs=2)
    for _ in range(130):
        b.xor_const(0, 0, rng.integers(0, 2, n))
    b.eq_const(1, 0, rng.integers(0, 2, n))
    b.xor_const(0, 0, rng.integers(0, 2, n))
    b.add(0, 0, 1)
    return b.build()


class TestDifferential:
    def test_synthetic_program_all_ops(self):
        prog = _synthetic_program()
        x = _words(1, (16, 8))
        chained = pp.run_program(prog, x, backend="chained")
        fused = pp.run_program(prog, x, backend="megakernel")
        np.testing.assert_array_equal(np.asarray(chained), np.asarray(fused))

    def test_every_step_count_keccak_round(self):
        """Megakernel == chained at every prefix length of one unrolled
        Keccak round (the per-step differential), plus the full
        24-round rolled program."""
        flat = kk.megakernel_program().unroll()
        x = _bits(2, (1600, 2))
        for n_steps in range(1, 7):
            prefix = flat.prefix(n_steps)
            chained = pp.run_program(prefix, x, backend="chained")
            fused = pp.run_program(prefix, x, backend="megakernel")
            np.testing.assert_array_equal(
                np.asarray(chained), np.asarray(fused),
                err_msg=f"prefix {n_steps}")
        full = kk.megakernel_program()
        np.testing.assert_array_equal(
            np.asarray(pp.run_program(full, x, backend="chained")),
            np.asarray(pp.run_program(full, x, backend="megakernel")))

    def test_every_step_count_chacha_quarter_round(self):
        """Every prefix of the first ChaCha quarter-round (10 steps:
        permute/add/xor/rotlv interleavings) plus the full program."""
        flat = cc.megakernel_program().unroll()
        x = _words(3, (16, 4))
        for n_steps in range(1, 11):
            prefix = flat.prefix(n_steps)
            chained = pp.run_program(prefix, x, backend="chained")
            fused = pp.run_program(prefix, x, backend="megakernel")
            np.testing.assert_array_equal(
                np.asarray(chained), np.asarray(fused),
                err_msg=f"prefix {n_steps}")
        full = cc.megakernel_program()
        np.testing.assert_array_equal(
            np.asarray(pp.run_program(full, x, backend="chained")),
            np.asarray(pp.run_program(full, x, backend="megakernel")))

    @pytest.mark.parametrize("name", ["keccak", "gcm_seal", "many_consts"])
    def test_served_program_over_lane_blocks(self, name):
        """Megakernel == chained on whole served programs at 300 lanes:
        three 128-lane grid steps of the megakernel."""
        if name == "keccak":
            prog = kk.megakernel_program()
        elif name == "gcm_seal":
            _, prog, _ = gcm.gcm_program(16, 16)
        else:
            prog = _many_consts_program()
        x = _bits(6, (prog.n, 300))
        keys = _bits(8, (prog.key_rows, 300)) if prog.key_rows else None
        np.testing.assert_array_equal(
            np.asarray(pp.run_program(prog, x, keys=keys, backend="chained",
                                      pass_backend="reference")),
            np.asarray(pp.run_program(prog, x, keys=keys,
                                      backend="megakernel")))

    def test_weighted_real_program(self):
        rng = np.random.default_rng(5)
        idx = jnp.asarray(rng.integers(0, 8, (8, 2)), jnp.int32)
        w = jnp.asarray(rng.integers(1, 5, (8, 2)), jnp.int32)
        plan = xb.gather_plan(idx, 8, weights=w)
        b = pp.ProgramBuilder("weighted", 8, n_regs=2)
        b.permute(1, 0, plan)
        b.add(0, 0, 1)
        prog = b.build()
        x = jnp.asarray(rng.integers(0, 100, (8, 3)), jnp.int32)
        np.testing.assert_array_equal(
            np.asarray(pp.run_program(prog, x, backend="chained")),
            np.asarray(pp.run_program(prog, x, backend="megakernel")))

    def test_1d_payload_round_trips_shape(self):
        prog = kk.megakernel_program()
        x = _bits(4, 1600)
        out = pp.run_program(prog, x, backend="megakernel")
        assert out.shape == (1600,) and out.dtype == x.dtype


class TestTelemetry:
    def test_megakernel_one_launch_zero_passes(self):
        prog = kk.megakernel_program()
        x = _bits(0, (1600, 1))
        telemetry.reset()
        with telemetry.delta() as d:
            pp.run_program(prog, x, backend="megakernel")
        dd = d()
        assert dd["program_launches"] == 1
        assert dd["apply_calls"] == 0
        assert dd["program_passes_avoided"] == prog.passes == 72
        for b in ("einsum", "kernel", "sparse", "reference"):
            assert dd[f"apply_calls_{b}"] == 0

    def test_chained_counts_passes_not_launches(self):
        prog = kk.megakernel_program()
        x = _bits(0, (1600, 1))
        telemetry.reset()
        with telemetry.delta() as d:
            pp.run_program(prog, x, backend="chained")
        dd = d()
        assert dd["program_launches"] == 0
        assert dd["apply_calls"] == prog.passes
        assert dd["apply_calls_einsum"] == prog.passes

    def test_backend_split_regression(self):
        """The satellite fix: einsum passes and Pallas-kernel passes are
        separately countable (they used to fold into one total)."""
        plan = pa.identity_plan(8)
        x = jnp.arange(8, dtype=jnp.int32)
        telemetry.reset()
        with telemetry.delta() as d:
            xb.apply_plan(plan, x, backend="einsum")
            xb.apply_plan(plan, x, backend="kernel", interpret=True)
            xb.apply_plan(plan, x, backend="kernel", interpret=True)
            xb.apply_plan(plan, x, backend="reference")
        dd = d()
        assert dd["apply_calls"] == 4
        assert dd["apply_calls_einsum"] == 1
        assert dd["apply_calls_kernel"] == 2
        assert dd["apply_calls_reference"] == 1
        assert dd["apply_calls_sparse"] == 0

    def test_executable_cache_hits_across_calls(self):
        prog = kk.megakernel_program()
        x = _bits(0, (1600, 1))
        telemetry.reset()
        pp.run_program(prog, x, backend="megakernel")
        pp.run_program(prog, x, backend="megakernel")
        info = pp.program_cache_info()
        assert info["misses"] == 1 and info["hits"] == 1
        # a different payload width is a different executable
        pp.run_program(prog, _bits(0, (1600, 200)), backend="megakernel")
        assert pp.program_cache_info()["misses"] == 2


def _gf2_dense_program(n=200, k=6, seed=21):
    """A GF(2) plan with duplicate sources, DROP selects, and even,
    odd and negative weights, applied twice (once in place)."""
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, n, (n, k))
    idx[:, 1] = idx[:, 0]                      # a duplicate in every row
    idx[::7, -1] = pa.DROP
    w = rng.integers(-3, 5, (n, k))
    plan = xb.gather_plan(jnp.asarray(idx, jnp.int32), n,
                          weights=jnp.asarray(w, jnp.int32), semiring=GF2)
    b = pp.ProgramBuilder("gf2_dense", n, n_regs=2)
    b.permute(1, 0, plan)
    b.xor(0, 0, 1)
    b.permute(0, 0, plan)
    return b.build()


def _permute_plans(program):
    """The plan slot of each PERMUTE step, in step order."""
    return [s.plan for s in program.steps if s.op == pp.PERMUTE]


def _dense_gf2_plans(n, uses):
    """A program of len(uses) distinct GF(2) plans of 4 live selects a
    row, plan ``i`` applied ``uses[i]`` times."""
    rng = np.random.default_rng(5)
    b = pp.ProgramBuilder("budget", n, n_regs=2)
    for u in uses:
        plan = xb.gather_plan(
            jnp.asarray(rng.integers(0, n, (n, 4)), jnp.int32), n,
            semiring=GF2)
        for _ in range(u):
            b.permute(0, 0, plan)
    return b.build()


def _clmul_int(x: int, h: int) -> int:
    out = 0
    for i in range(x.bit_length()):
        if (x >> i) & 1:
            out ^= h << i
    return out


def _column_int(bits) -> int:
    return sum(int(b) << i for i, b in enumerate(bits))


class TestKeySteps:
    """XOR_KEY and CLMUL: the per-lane key operand, on both executors."""

    N, LANES = 392, 5

    def _program(self):
        b = pp.ProgramBuilder("keyed", self.N, n_regs=2)
        b.xor_key(0, 2, row=8)           # rows 8..135 ^= key block 2
        b.clmul(0, 1, row=128)           # rows 128..383 = x (x) block 1
        b.xor(1, 0, 0)
        return b.build()

    @pytest.mark.parametrize("backend", ["chained", "megakernel"])
    def test_clmul_matches_python_carryless_product(self, backend):
        prog = self._program()
        assert prog.key_rows == 3 * pp.KEY_BLOCK
        rng = np.random.default_rng(21)
        x = rng.integers(0, 2, (self.N, self.LANES))
        keys = rng.integers(0, 2, (prog.key_rows, self.LANES))
        got = np.asarray(pp.run_program(prog, jnp.asarray(x, jnp.int32),
                                        keys=jnp.asarray(keys, jnp.int32),
                                        backend=backend))
        want = x.copy()
        want[8:136] ^= keys[256:384]
        for lane in range(self.LANES):
            prod = _clmul_int(_column_int(want[128:256, lane]),
                              _column_int(keys[128:256, lane]))
            want[128:384, lane] = [(prod >> k) & 1 for k in range(256)]
        np.testing.assert_array_equal(got, want)

    def test_keys_padded_to_the_lane_block(self):
        prog = self._program()
        x = _bits(3, (self.N, 2))
        keys = _bits(4, (prog.key_rows, pp.lane_pad(2)))
        for backend in ("megakernel", "chained"):
            np.testing.assert_array_equal(
                np.asarray(pp.run_program(prog, x, keys=keys,
                                          backend=backend)),
                np.asarray(pp.run_program(prog, x, keys=keys[:, :2],
                                          backend="chained")))

    def test_key_operand_is_checked(self):
        prog = self._program()
        x = _bits(5, (self.N, 2))
        with pytest.raises(ValueError, match="key operand"):
            pp.run_program(prog, x)
        with pytest.raises(ValueError, match="key operand"):
            pp.run_program(prog, x, keys=_bits(5, (128, 2)))
        with pytest.raises(ValueError, match="key operand"):
            pp.run_program(prog, x, keys=_bits(5, (prog.key_rows, 3)))
        with pytest.raises(ValueError, match="reads no key operand"):
            pp.run_program(_synthetic_program(), _words(1, (16, 2)),
                           keys=_bits(5, (128, 2)))

    @pytest.mark.parametrize("step", [
        pp.Step(pp.CLMUL, 0, 1, key=0, row=0),      # not in place
        pp.Step(pp.XOR_KEY, 0, 0, key=0, row=4),    # unaligned window
        pp.Step(pp.CLMUL, 0, 0, key=0, row=144),    # past the state
        pp.Step(pp.XOR_KEY, 0, 0, row=0)])          # no key block
    def test_rejects_bad_key_step(self, step):
        with pytest.raises(ValueError, match="key step"):
            pp.PlanProgram("bad", self.N, (step,), (), None, 2)


class TestDensePermute:
    """Dense GF(2) PERMUTEs (one MXU product) against the chained
    executor, the rule that picks them, and the launch counters."""

    def test_keccak_full_program(self):
        prog = kk.megakernel_program()
        assert pp.dense_slots(prog) == (0, -1, -1)
        x = _bits(31, (1600, 5))
        np.testing.assert_array_equal(
            np.asarray(pp.run_program(prog, x, backend="chained")),
            np.asarray(pp.run_program(prog, x, backend="megakernel")))

    @pytest.mark.parametrize("open_mode", [False, True])
    @pytest.mark.parametrize("pt_len,aad_len", [(16, 16), (17, 5)])
    def test_gcm_programs(self, pt_len, aad_len, open_mode):
        """Seal and open, m=1 and m=2 with a 1-byte last block."""
        _, prog, _ = gcm.gcm_program(pt_len, aad_len, open_mode=open_mode)
        assert max(pp.dense_slots(prog)) >= 0
        x = _bits(32, (prog.n, 3))
        keys = _bits(33, (prog.key_rows, 3))
        np.testing.assert_array_equal(
            np.asarray(pp.run_program(prog, x, keys=keys, backend="chained",
                                      pass_backend="reference")),
            np.asarray(pp.run_program(prog, x, keys=keys,
                                      backend="megakernel")))

    @pytest.mark.parametrize("values", ["bits", "int32", "uint32"])
    def test_random_gf2_plan_parity(self, values):
        """Duplicates, even weights and wide state values: only bit 0
        of each product counts, as in the walk.  n = 200 is not a
        multiple of 128; 300 lanes are three lane blocks."""
        prog = _gf2_dense_program()
        assert pp.dense_slots(prog) == (0,)
        rng = np.random.default_rng(33)
        if values == "bits":
            x = jnp.asarray(rng.integers(0, 2, (200, 300)), jnp.int32)
        elif values == "int32":
            x = jnp.asarray(rng.integers(-1000, 1000, (200, 300)),
                            jnp.int32)
        else:
            x = _words(34, (200, 300))
        chained = pp.run_program(prog, x, backend="chained",
                                 pass_backend="reference")
        fused = pp.run_program(prog, x, backend="megakernel")
        assert fused.dtype == x.dtype
        np.testing.assert_array_equal(np.asarray(chained),
                                      np.asarray(fused))

    def test_table_is_weight_parity(self):
        prog = _gf2_dense_program(n=8, k=5, seed=3)
        n_pad, control, _ = pp.encode_program(prog)
        assert n_pad == 128 and control[-1].shape == (1, 128, 128)
        idx = np.asarray(prog.plans[0].idx)
        w = np.asarray(prog.plans[0].weights)
        want = np.zeros((128, 128), np.int64)
        for i in range(8):
            for j in range(5):
                if 0 <= idx[i, j] < 8:
                    want[i, idx[i, j]] += w[i, j]
        np.testing.assert_array_equal(
            np.asarray(control[-1][0], np.float32), want % 2)

    def test_selection_rule(self):
        keccak = kk.megakernel_program()
        steps = _permute_plans(keccak)       # theta-rho-pi, chi, chi
        assert [pp.dense_slots(keccak)[p] for p in steps] == [0, -1, -1]
        assert keccak.plans[steps[1]].semiring is not GF2

        _, seal, _ = gcm.gcm_program(17, 5)
        slots = pp.dense_slots(seal)
        plans = _permute_plans(seal)
        # d1, d2, ctr, then round 1: nspread, psel, hirep, nfold, linear
        d1, _, _, nspread, psel, hirep, nfold, linear = plans[:8]
        absorbs = [s.plan for s in seal.steps
                   if s.op == pp.PERMUTE and (s.dst, s.a) == (2, 1)]
        full, masked = absorbs
        assert seal.plans[nspread].semiring is not GF2
        # With H per lane, an absorb reads about 6 selects a factor row
        # (the reduction and the ciphertext bit), not the 64 of a baked
        # multiply-by-H matrix: only the S-box's psel is dense.
        assert slots[psel] >= 0
        assert sorted(s for s in slots if s >= 0) == [0]
        for walked in (d1, nspread, hirep, nfold, linear, full, masked,
                       plans[-1]):
            assert slots[walked] == -1

        chacha = cc.megakernel_program()
        assert set(pp.dense_slots(chacha)) == {-1}
        n_pad, control, _ = pp.encode_program(chacha)
        assert n_pad == 64 and len(control) == 4

    def test_sparse_and_unused_gf2_plans_walk(self):
        rng = np.random.default_rng(2)
        sparse = xb.gather_plan(
            jnp.asarray(rng.integers(0, 64, (64, 3)), jnp.int32), 64,
            semiring=GF2)
        dense = xb.gather_plan(
            jnp.asarray(rng.integers(0, 64, (64, 4)), jnp.int32), 64,
            semiring=GF2)
        b = pp.ProgramBuilder("t", 64, n_regs=2)
        b.permute(1, 0, sparse)
        b.plan_slot(dense)                  # in the table, never applied
        assert pp.dense_slots(b.build()) == (-1, -1)

    def test_budget_takes_plans_by_entries_times_uses(self):
        """At n = 2688 three tables fit the budget and a fourth does
        not: the plan applied least walks."""
        prog = _dense_gf2_plans(2688, uses=[1, 3, 4, 2])
        assert pp.dense_slots(prog) == (-1, 1, 0, 2)

    def test_launch_counters(self):
        keccak = kk.megakernel_program()
        telemetry.reset()
        with telemetry.delta() as d:
            pp.run_program(keccak, _bits(0, (1600, 1)), backend="megakernel")
        dd = d()
        assert dd["megakernel_entries_dense"] == 17600 * 24
        assert dd["megakernel_entries_walked"] == 2 * 1600 * 24

        chacha = cc.megakernel_program()
        with telemetry.delta() as d:
            pp.run_program(chacha, _words(0, (16, 2)), backend="megakernel")
            pp.run_program(chacha, _words(1, (16, 2)), backend="megakernel")
        dd = d()
        assert dd["megakernel_entries_dense"] == 0
        live = sum(int(((np.asarray(chacha.plans[p].idx) >= 0)).sum())
                   for p in _permute_plans(chacha))
        assert dd["megakernel_entries_walked"] == 2 * 10 * live


class TestKeccakMegakernel:
    def test_matches_per_round_path(self):
        bits = _bits(11, 1600)
        np.testing.assert_array_equal(
            np.asarray(kk.keccak_f1600(bits)),
            np.asarray(kk.keccak_f1600(bits, backend="megakernel")))

    def test_batched_lanes_match(self):
        bits = _bits(12, (8, 1600))
        np.testing.assert_array_equal(
            np.asarray(kk.keccak_f1600(bits)),
            np.asarray(kk.keccak_f1600(bits, backend="megakernel")))

    def test_sha3_sponges_match_hashlib(self):
        msg = b"one launch per permutation"
        assert kk.sha3_256(msg, backend="megakernel") == \
            hashlib.sha3_256(msg).digest()
        assert kk.sha3_512(msg, backend="megakernel") == \
            hashlib.sha3_512(msg).digest()
        assert kk.shake_256(msg, 64, backend="megakernel") == \
            hashlib.shake_256(msg).digest(64)

    def test_batched_sponge_megakernel(self):
        msgs = [bytes([i]) * 50 for i in range(4)]
        got = kk.sha3_256_batched(msgs, backend="megakernel")
        assert got == [hashlib.sha3_256(m).digest() for m in msgs]

    def test_one_launch_per_permutation(self):
        """Acceptance: SHA3-256 of a 3-block message runs exactly 3
        permutations = 3 launches, zero crossbar passes."""
        msg = bytes(290)  # 3 blocks at rate 136
        telemetry.reset()
        with telemetry.delta() as d:
            digest = kk.sha3_256(msg, backend="megakernel")
        dd = d()
        assert digest == hashlib.sha3_256(msg).digest()
        assert dd["program_launches"] == 3
        assert dd["apply_calls"] == 0

    def test_theta_is_a_crossbar_pass(self):
        """θ alone, as the registered k=11 GF(2) plan, equals the
        arithmetic θ implementation."""
        bits = _bits(13, 1600)
        a = bits.reshape(1, 5, 5, 64)
        want = kk._theta(a).reshape(1600)
        got = xb.apply_plan(kk.theta_plan(), bits)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

    def test_fixed_latency_contract(self):
        for seed in range(3):
            kk.keccak_f1600(_bits(seed, 1600), backend="megakernel",
                            fixed_latency=True)
        sigs = [k for k in REGISTRY._observed
                if k[0] == ("keccak_f1600", "megakernel")]
        assert len(sigs) == 1
        calls, plan_fps, launches, prog_fps = REGISTRY._observed[sigs[0]]
        assert calls == 0 and launches == 1
        assert prog_fps == (
            REGISTRY.program_fingerprint(kk.MEGAKERNEL_PROGRAM_KEY),)

    def test_constant_time_audit_over_program(self):
        prog = kk.megakernel_program()
        out = REGISTRY.audit_constant_time(
            "keccak-megakernel",
            lambda x: pp.run_program(prog, x, backend="megakernel"),
            jnp.zeros((1600, 4), jnp.int32))
        assert out.shape == (1600, 4)


class TestChaChaMegakernel:
    KEY = bytes(range(32))
    NONCE = bytes.fromhex("000000090000004a00000000")

    def test_rfc8439_vector(self):
        got = cc.chacha20_block(self.KEY, 1, self.NONCE,
                                backend="megakernel")
        assert got == cc.chacha20_block(self.KEY, 1, self.NONCE)
        assert got[:16] == bytes.fromhex(
            "10f1e7e4d13b5915500fdd1fa32071c4")

    def test_batched_counter_blocks(self):
        assert cc.chacha20_blocks(self.KEY, 7, self.NONCE, 5,
                                  backend="megakernel") == \
            cc.chacha20_blocks(self.KEY, 7, self.NONCE, 5)

    def test_one_launch_zero_passes(self):
        telemetry.reset()
        with telemetry.delta() as d:
            cc.chacha20_blocks(self.KEY, 0, self.NONCE, 4,
                               backend="megakernel", fixed_latency=True)
        dd = d()
        assert dd["program_launches"] == 1 and dd["apply_calls"] == 0

    def test_encrypt_roundtrip(self):
        pt = b"megakernel ARX roundtrip" * 11
        ct = cc.chacha20_encrypt(self.KEY, 3, self.NONCE, pt,
                                 backend="megakernel")
        assert cc.chacha20_encrypt(self.KEY, 3, self.NONCE, ct,
                                   backend="megakernel") == pt


class TestProgramRegistry:
    def test_double_register_raises(self):
        kk.megakernel_program()
        with pytest.raises(ValueError, match="already registered"):
            REGISTRY.register_program(kk.MEGAKERNEL_PROGRAM_KEY,
                                      _synthetic_program())

    def test_unknown_program_names_registry(self):
        with pytest.raises(KeyError, match="crypto"):
            REGISTRY.program("no/such/program")

    def test_fingerprint_stable_across_calls(self):
        kk.megakernel_program()
        fp1 = REGISTRY.program_fingerprint(kk.MEGAKERNEL_PROGRAM_KEY)
        fp2 = REGISTRY.program_fingerprint(kk.MEGAKERNEL_PROGRAM_KEY)
        assert fp1 == fp2
        assert fp1[2] == 24  # trip count is part of the identity

    def test_fingerprint_distinguishes_programs(self):
        reg = StaticPlanRegistry("unit")
        reg.register_program("a", _synthetic_program())
        shorter = _synthetic_program().prefix(5)
        reg.register_program("b", shorter)
        assert reg.program_fingerprint("a") != reg.program_fingerprint("b")

    def test_program_drift_raises(self):
        """An extra launch inside an observed region is latency drift."""
        prog = kk.megakernel_program()
        x = _bits(0, (1600, 1))
        with REGISTRY.observe("unit-prog-drift",
                              program_keys=(kk.MEGAKERNEL_PROGRAM_KEY,)):
            pp.run_program(prog, x, backend="megakernel")
        with pytest.raises(FixedLatencyError, match="fixed-latency"):
            with REGISTRY.observe("unit-prog-drift",
                                  program_keys=(kk.MEGAKERNEL_PROGRAM_KEY,)):
                pp.run_program(prog, x, backend="megakernel")
                pp.run_program(prog, x, backend="megakernel")

    def test_expected_launch_count_enforced(self):
        prog = kk.megakernel_program()
        x = _bits(0, (1600, 1))
        with pytest.raises(FixedLatencyError, match="program launches"):
            with REGISTRY.observe("unit-launches",
                                  expect_program_launches=2):
                pp.run_program(prog, x, backend="megakernel")

    def test_traced_plan_control_rejected(self):
        reg = StaticPlanRegistry("unit")

        @jax.jit
        def build(idx):
            plan = xb.gather_plan(idx, 4)
            with pytest.raises(ValueError, match="traced"):
                # The IR itself refuses traced control at construction —
                # a traced program can never reach the registry.
                pp.PlanProgram(
                    "traced", 4, (pp.Step(pp.PERMUTE, 0, 0, plan=0),),
                    (plan,), None, 2)
            return idx

        build(jnp.arange(4, dtype=jnp.int32))


class TestBenchmarkDiscovery:
    def test_run_discovers_every_bench_module(self):
        """CI satellite: auto-discovery picks up the new benchmark and
        every discovered module exposes a run() entry point."""
        import importlib
        from benchmarks import run as harness
        mods = harness.discover()
        assert "bench_keccak_fused" in mods
        for name in mods:
            mod = importlib.import_module(f"benchmarks.{name}")
            assert callable(getattr(mod, "run", None)), name
