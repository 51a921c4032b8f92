"""Pallas megakernel: execute a whole ``PlanProgram`` in one launch.

The per-pass kernels (``crossbar_permute.py``) rebuild a one-hot tile
per grid step and contract on the MXU — ideal when one pass is the
whole workload.  A crypto permutation is the opposite regime: dozens of
*small* passes (1600-row Keccak states, 16-word ChaCha states)
interleaved with elementwise arithmetic, where the cost is not the
FLOPs but the HBM round-trip of the state between every step.

This kernel inverts the loop: each lane block of the state is DMA'd
into VMEM **once**, a register file of ``(n, lanes)`` buffers lives
entirely on-chip, and the program executes as a **bytecode VM** over
the resident registers:

* the step stream is program data in HBM, walked in SMEM chunks by a
  ``fori_loop``: each step's eight int32 words (opcode, register
  wiring, plan/const slot) are scalar reads, and the opcode selects
  its op body with ``pl.when`` — every body is compiled once, however
  many steps or rounds the program has;
* a PERMUTE's work is either its plan's live entries or one product:
  - the **walk** goes over the plan's select entries — the flat list
    of ``(dst row, src row, weight)`` triples of its live selects,
    DMA'd into SMEM a chunk at a time — and folds each gathered source
    row into an accumulator register: integer XOR of bit 0 for GF(2),
    wrapping add for REAL.  A row load at a scalar offset is the gather
    the TPU's vector unit offers; the work is the plan's live entries,
    never ``rows x k`` of DROP padding, at a fixed cost per entry;
  - a **dense** GF(2) plan (chosen by the encoder: at least
    ``DENSE_MIN_SELECTS_PER_ROW`` live selects per state row, and its
    table within ``DENSE_VMEM_BUDGET_BYTES``) runs as one MXU product
    of its 0/1 matrix with bit 0 of the source register, parity-folded:
    ``dst = (T @ (src & 1)) & 1``.  Row sums are at most ``n`` < 2^24,
    so the f32 accumulation is exact, and the time is one product
    whatever the plan's entry count.  The tables are DMA'd into VMEM
    once per launch, on the first lane block;
* the elementwise ops (XOR/AND/ANDN/ADD/ROTLV/XOR_CONST/EQ_CONST) run
  over the registers in row tiles; a constant row reaches them as a
  ``(rows, 1)`` column cut out of a lane-transposed constants block
  (constant ``c`` is lane ``c % 128`` of block ``c // 128``), DMA'd
  into VMEM when a step first needs that block;
* a ``fori_loop`` supplies the trip count, with per-round constants
  indexed as ``const + round * const_stride``;
* the key steps read the launch's per-lane **key operand**, DMA'd into
  VMEM with each lane block like the state: ``XOR_KEY`` XORs one of its
  128-row blocks into a 128-row window, and ``CLMUL`` forms the
  carry-less product of a 128-row bit window with a key block, lane by
  lane (bit-sliced: rows are coefficients, lanes are records).  The
  window is copied once at each of the 8 sublane offsets; each 8-row
  group ``q`` of the key block then XORs ``copy[s] & key[8q + s]`` into
  the 136-row accumulator slice at row ``8q``, aligned, so a product is
  128 AND/XOR passes over 17 row tiles whatever the values.

The unit of VMEM residency is one lane block: a grid over the payload
(lane) axis runs the complete program on each block in turn.  Lanes
are independent by construction, so the block width changes no result;
``lane_block`` picks the widest block whose register file fits VMEM.

The schedule is a function of the program stream alone and never of
payload values: every loop bound is program data, so one program's
launch has a fixed latency per lane block.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Opcode numbering: the op bodies below are dispatched on this tuple's
# indices, and core.plan_program's step-stream encoder asserts its OPS
# order matches it — insert or reorder an op in one place without the
# other and programs fail loudly at build time, never silently.
# ("eq_const" appended last so pre-existing encoded streams keep their
# numbering.)
OPCODES = ("permute", "xor", "and", "andn", "add", "rotlv", "xor_const",
           "eq_const", "xor_key", "clmul")

# Encoded layouts.  HBM arrays are 1-D and DMA'd in chunks whose start
# and size are multiples of 1024 words (the 1-D HBM tiling).
STEP_WORDS = 8           # (op, dst, a, b, plan, const, key, row)
META_WORDS = 4           # per plan: (offset, count, xor fold, dense slot)
STEP_CHUNK = 128         # steps per SMEM chunk (1024 words)
ENTRY_WORDS = 3          # (dst row, src row, weight)
ENTRY_CHUNK = 2048       # entries per SMEM chunk (6144 words, 24 KiB)
HBM_ALIGN = 1024         # words
ROW_TILE = 64            # rows per elementwise tile; n_pad is a multiple
LANES = 128
# Scoped VMEM the kernel may ask for: the TPU v5e core has 128 MiB.
VMEM_CAP_BYTES = 128 * 1024 * 1024
_VMEM_SLACK_BYTES = 4 * 1024 * 1024
# Dense GF(2) PERMUTEs.  A walked entry costs about 13 ns on a v5e at
# 128 lanes, whatever the plan (12.7 ns Keccak-f, 13.0 ns GCM seal).
# One product of an (n x n) 0/1 table with the (n x 128) bit block is
# 256 n^2 MXU operations: 5.2e-12 n^2 s at a quarter of the 197 TFLOP/s
# bf16 peak.  The product wins once a plan has 4e-4 n live selects per
# row: 0.7 at n = 1664, 1.1 at n = 2688, 2.0 at n = 5,000 (the largest
# table the budget below admits).  Four per row keeps twice that margin.
DENSE_MIN_SELECTS_PER_ROW = 4
# VMEM the dense tables of one program may take, out of VMEM_CAP_BYTES:
# two GCM tables (2 x 14.45 MB at n = 2688) with room for a third.
DENSE_VMEM_BUDGET_BYTES = 48 * 1024 * 1024
DENSE_DTYPE = jnp.bfloat16   # 0/1 exact; the MXU's native input
DENSE_ROWS = 128             # output rows per product tile
KEY_BLOCK = 128              # key-operand rows per key block
SUBLANES = 8                 # CLMUL's shifted copies of its window


def control_digest(steps, consts, plan_parts=()) -> str:
    """Content digest of one program's kernel-visible control state:
    the encoded step stream, the constants table, and the per-plan
    idx/weight arrays, from which the entry list and the dense tables
    are built.  Salted with the opcode numbering and the dense-plan
    rule, so a reordered OPCODES tuple or another rule invalidates every
    sealed digest rather than letting an old stream verify against a
    renumbered switch or differently chosen tables."""
    from repro.core import integrity
    rule = (f"dense>={DENSE_MIN_SELECTS_PER_ROW}/row,"
            f"{DENSE_VMEM_BUDGET_BYTES}B,{jnp.dtype(DENSE_DTYPE).name}")
    return integrity.content_digest(
        ("|".join(OPCODES), rule, steps, consts) + tuple(plan_parts))


def dense_table_bytes(n_pad: int) -> int:
    """VMEM (and HBM) bytes of one dense plan's (n_pad, n_pad) table."""
    return n_pad * n_pad * jnp.dtype(DENSE_DTYPE).itemsize


def vmem_bytes(n_pad: int, lane_block: int, n_regs: int,
               itemsize: int, n_dense: int = 0, key_rows: int = 0) -> int:
    """VMEM one launch asks for: the register file plus the PERMUTE
    accumulator at ``lane_block`` lanes, one constants block, with
    ``n_dense`` dense plans their tables and the source's bit block, and
    with a key operand of ``key_rows`` rows its block and CLMUL's
    shifted window copies."""
    need = ((n_regs + 1) * n_pad * lane_block * itemsize
            + n_pad * LANES * 4 + _VMEM_SLACK_BYTES)
    if n_dense:
        need += (n_dense * dense_table_bytes(n_pad)
                 + n_pad * lane_block * jnp.dtype(DENSE_DTYPE).itemsize)
    if key_rows:
        need += ((key_rows + SUBLANES * (KEY_BLOCK + SUBLANES))
                 * lane_block * itemsize)
    return need


def lane_block(n_pad: int, d_pad: int, n_regs: int, itemsize: int,
               n_dense: int = 0, key_rows: int = 0) -> int:
    """The widest lane block (a multiple of 128 dividing ``d_pad``, at
    most 1024) whose register file, dense tables and key operand fit the
    VMEM cap.  Raises when not even 128 lanes fit: the state is too tall
    for one core's VMEM."""
    for q in (8, 4, 2, 1):
        blk = LANES * q
        if (d_pad % blk == 0 and vmem_bytes(n_pad, blk, n_regs, itemsize,
                                            n_dense, key_rows)
                <= VMEM_CAP_BYTES):
            return blk
    raise ValueError(
        f"plan program state of {n_pad} rows x {n_regs} registers needs "
        f"{vmem_bytes(n_pad, LANES, n_regs, itemsize, n_dense, key_rows)} "
        f"bytes of VMEM at {LANES} lanes; the cap is {VMEM_CAP_BYTES}")


def _rotlv(v, amt):
    """Per-row rotate-left; amount 0 is the identity (the masked ``&``
    keeps the ``v >> bits`` shift out of UB territory at amt == 0)."""
    bits = jnp.iinfo(v.dtype).bits
    return (v << amt) | (v >> ((bits - amt) & (bits - 1)))


def _kernel(meta_ref, steps_hbm, ent_hbm, consts_hbm, x_hbm, *refs,
            n_steps, n_regs, rounds, const_stride, n_dense, keyed):
    """The VM over one lane block: rounds { step chunks { steps } }.

    With ``n_dense`` dense plans, ``refs`` holds their tables' HBM
    operand before the output and, after the common scratch, the tables'
    VMEM copy and the bf16 bit block of a product's source.  With a key
    operand (``keyed``), its HBM operand follows, and its VMEM block and
    CLMUL's shifted window copies close the scratch."""
    refs = list(refs)
    dense_hbm = refs.pop(0) if n_dense else None
    keys_hbm = refs.pop(0) if keyed else None
    o_hbm, regs, acc, cblk, step_buf, ent_buf, cur_blk = refs[:7]
    tables, bits = refs[7:9] if n_dense else (None, None)
    kregs, shifted = refs[-2:] if keyed else (None, None)
    n_pad, width = acc.shape
    dtype = acc.dtype
    n_tiles = n_pad // ROW_TILE
    lanes = pl.ds(pl.multiple_of(pl.program_id(0) * width, LANES), width)

    def tiles(body):
        def run(t, carry):
            body(pl.ds(pl.multiple_of(t * ROW_TILE, ROW_TILE), ROW_TILE))
            return carry
        jax.lax.fori_loop(0, n_tiles, run, 0)

    if n_dense:
        @pl.when(pl.program_id(0) == 0)
        def _():
            pltpu.sync_copy(dense_hbm, tables)

    pltpu.sync_copy(x_hbm.at[:, lanes], regs.at[0])
    if keyed:
        pltpu.sync_copy(keys_hbm.at[:, lanes], kregs)
    for r in range(1, n_regs):
        def zero(rows, r=r):
            regs[r, rows, :] = jnp.zeros((ROW_TILE, width), dtype)
        tiles(zero)
    cur_blk[0] = -1

    def const_column(c):
        """Load constant ``c``'s block if needed; return a per-tile
        reader of its (ROW_TILE, 1) column."""
        blk = c // LANES

        @pl.when(cur_blk[0] != blk)
        def _():
            pltpu.sync_copy(consts_hbm.at[blk], cblk)
            cur_blk[0] = blk

        lane = c % LANES

        def column(rows):
            tile = cblk[rows, :]
            hit = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1) == lane
            col = jnp.sum(jnp.where(hit, tile, 0), axis=1, keepdims=True)
            return col.astype(dtype)
        return column

    def walk(dst, a, p):
        off = meta_ref[META_WORDS * p]
        count = meta_ref[META_WORDS * p + 1]
        is_xor = meta_ref[META_WORDS * p + 2]

        def zero(rows):
            acc[rows, :] = jnp.zeros((ROW_TILE, width), dtype)
        tiles(zero)

        def fold(xor):
            def body(e, carry):
                d = ent_buf[ENTRY_WORDS * e]
                s = ent_buf[ENTRY_WORDS * e + 1]
                w = ent_buf[ENTRY_WORDS * e + 2].astype(dtype)
                g = regs[a, pl.ds(s, 1), :] * w
                cur = acc[pl.ds(d, 1), :]
                acc[pl.ds(d, 1), :] = (cur ^ (g & 1)) if xor else (cur + g)
                return carry
            return body

        def chunk(ci, carry):
            start = pl.multiple_of(off + ci * (ENTRY_WORDS * ENTRY_CHUNK),
                                   HBM_ALIGN)
            pltpu.sync_copy(
                ent_hbm.at[pl.ds(start, ENTRY_WORDS * ENTRY_CHUNK)], ent_buf)
            m = jnp.minimum(ENTRY_CHUNK, count - ci * ENTRY_CHUNK)

            @pl.when(is_xor != 0)
            def _():
                jax.lax.fori_loop(0, m, fold(True), 0)

            @pl.when(is_xor == 0)
            def _():
                jax.lax.fori_loop(0, m, fold(False), 0)
            return carry

        jax.lax.fori_loop(0, pl.cdiv(count, ENTRY_CHUNK), chunk, 0)

        def store(rows):
            regs[dst, rows, :] = acc[rows, :]
        tiles(store)

    def product(dst, a, slot):
        """dst = (T[slot] @ (regs[a] & 1)) & 1 over every row: the
        source's bits are copied out first, so ``dst`` may be ``a``."""
        def to_bits(rows):
            bit = (regs[a, rows, :] & 1).astype(jnp.int32)
            bits[rows, :] = bit.astype(jnp.float32).astype(DENSE_DTYPE)
        tiles(to_bits)

        def body(t, carry):
            rows = pl.ds(pl.multiple_of(t * DENSE_ROWS, DENSE_ROWS),
                         DENSE_ROWS)
            y = jnp.dot(tables[slot, rows, :], bits[...],
                        preferred_element_type=jnp.float32)
            regs[dst, rows, :] = (y.astype(jnp.int32) & 1).astype(dtype)
            return carry
        jax.lax.fori_loop(0, n_pad // DENSE_ROWS, body, 0)

    def permute(dst, a, p):
        if not n_dense:
            walk(dst, a, p)
            return
        slot = meta_ref[META_WORDS * p + 3]

        @pl.when(slot < 0)
        def _():
            walk(dst, a, p)

        @pl.when(slot >= 0)
        def _():
            product(dst, a, slot)

    def elementwise(dst, a, b, fn):
        def body(rows):
            regs[dst, rows, :] = fn(regs[a, rows, :], regs[b, rows, :])
        tiles(body)

    def with_const(dst, a, c, fn):
        column = const_column(c)

        def body(rows):
            regs[dst, rows, :] = fn(regs[a, rows, :], column(rows))
        tiles(body)

    def xor_key(dst, k, row):
        rows = pl.ds(pl.multiple_of(row, SUBLANES), KEY_BLOCK)
        block = pl.ds(pl.multiple_of(k * KEY_BLOCK, KEY_BLOCK), KEY_BLOCK)
        regs[dst, rows, :] = regs[dst, rows, :] ^ kregs[block, :]

    def clmul(dst, k, row):
        """regs[dst] rows [row, row + 256) = bits [row, row + 128) times
        key block k, carry-less: copy s of the window sits s rows down
        in ``shifted[s]``, so the term of key row 8q + s lands on the
        aligned accumulator slice [8q, 8q + 136)."""
        r0 = pl.multiple_of(row, SUBLANES)
        window = regs[dst, pl.ds(r0, KEY_BLOCK), :] & 1
        edge = jnp.zeros((SUBLANES, width), dtype)
        for s in range(SUBLANES):
            shifted[s, pl.ds(0, SUBLANES), :] = edge
            shifted[s, pl.ds(KEY_BLOCK, SUBLANES), :] = edge
            shifted[s, pl.ds(s, KEY_BLOCK), :] = window
        acc[pl.ds(0, 2 * KEY_BLOCK), :] = jnp.zeros((2 * KEY_BLOCK, width),
                                                   dtype)
        h0 = pl.multiple_of(k * KEY_BLOCK, KEY_BLOCK)

        def group(q, carry):
            base = pl.multiple_of(q * SUBLANES, SUBLANES)
            rows = pl.ds(base, KEY_BLOCK + SUBLANES)
            p = acc[rows, :]
            for s in range(SUBLANES):
                h = kregs[pl.ds(h0 + base + s, 1), :] & 1
                p = p ^ (shifted[s] & h)
            acc[rows, :] = p
            return carry
        jax.lax.fori_loop(0, KEY_BLOCK // SUBLANES, group, 0)
        regs[dst, pl.ds(r0, 2 * KEY_BLOCK), :] = acc[pl.ds(0, 2 * KEY_BLOCK),
                                                     :]

    binary = {"xor": lambda u, v: u ^ v,
              "and": lambda u, v: u & v,
              "andn": lambda u, v: ~u & v,
              "add": lambda u, v: u + v}
    with_c = {"rotlv": _rotlv,
              "xor_const": lambda u, col: u ^ col,
              "eq_const": lambda u, col: jnp.where(
                  u == col, jnp.ones_like(u), jnp.zeros_like(u))}

    def step(rnd, base):
        op = step_buf[base]
        dst = step_buf[base + 1]
        a = step_buf[base + 2]
        b = step_buf[base + 3]
        p = step_buf[base + 4]
        c = step_buf[base + 5] + rnd * const_stride
        k = step_buf[base + 6]
        row = step_buf[base + 7]
        for code, name in enumerate(OPCODES):
            if name in ("xor_key", "clmul") and not keyed:
                continue
            @pl.when(op == code)
            def _(name=name):
                if name == "permute":
                    permute(dst, a, p)
                elif name == "xor_key":
                    xor_key(dst, k, row)
                elif name == "clmul":
                    clmul(dst, k, row)
                elif name in binary:
                    elementwise(dst, a, b, binary[name])
                else:
                    with_const(dst, a, c, with_c[name])

    def round_body(rnd, carry):
        def chunk(ci, carry):
            start = pl.multiple_of(ci * (STEP_WORDS * STEP_CHUNK), HBM_ALIGN)
            pltpu.sync_copy(steps_hbm.at[pl.ds(start, STEP_WORDS * STEP_CHUNK)],
                            step_buf)
            m = jnp.minimum(STEP_CHUNK, n_steps - ci * STEP_CHUNK)

            def body(si, carry):
                step(rnd, si * STEP_WORDS)
                return carry
            jax.lax.fori_loop(0, m, body, 0)
            return carry
        jax.lax.fori_loop(0, pl.cdiv(n_steps, STEP_CHUNK), chunk, 0)
        return carry

    jax.lax.fori_loop(0, rounds, round_body, 0)
    pltpu.sync_copy(regs.at[0], o_hbm.at[:, lanes])


def plan_program_pallas(
    state: jax.Array,
    steps: jax.Array,
    entries: jax.Array,
    meta: jax.Array,
    consts: jax.Array,
    dense: Optional[jax.Array] = None,
    *,
    keys: Optional[jax.Array] = None,
    n_steps: int,
    n_regs: int,
    rounds: int = 1,
    const_stride: int = 0,
    interpret: bool = False,
) -> jax.Array:
    """Raw megakernel entry; operands come from ``encode_*`` in
    ``core.plan_program``.

    state: (n_pad, d_pad), n_pad a multiple of ROW_TILE (of 128 with
    dense plans) and d_pad of 128; steps: flat int32, STEP_WORDS per
    step, padded to whole STEP_CHUNKs; entries: flat int32 (dst, src,
    weight) triples, each plan's run starting at a multiple of HBM_ALIGN
    words, with one ENTRY_CHUNK of tail padding; meta: (META_WORDS *
    n_plans,) int32 per-plan (entry offset, entry count, 1 = GF(2) XOR
    fold, dense slot or -1 to walk); consts: (n_blocks, n_pad, 128)
    int32, constant ``c`` in lane ``c % 128`` of block ``c // 128``;
    dense: None, or (n_dense, n_pad, n_pad) DENSE_DTYPE 0/1 tables, one
    per dense slot; keys: None, or the (key_rows, d_pad) per-lane key
    operand in state.dtype, key_rows a multiple of KEY_BLOCK, for
    programs with key steps.  Returns (n_pad, d_pad) in state.dtype.
    """
    n_pad, d_pad = state.shape
    n_dense = 0 if dense is None else dense.shape[0]
    key_rows = 0 if keys is None else keys.shape[0]
    itemsize = state.dtype.itemsize
    width = lane_block(n_pad, d_pad, n_regs, itemsize, n_dense, key_rows)
    kernel = functools.partial(
        _kernel, n_steps=n_steps, n_regs=n_regs, rounds=rounds,
        const_stride=const_stride, n_dense=n_dense, keyed=bool(key_rows))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    scratch = [
        pltpu.VMEM((n_regs, n_pad, width), state.dtype),
        pltpu.VMEM((n_pad, width), state.dtype),
        pltpu.VMEM((n_pad, LANES), jnp.int32),
        pltpu.SMEM((STEP_WORDS * STEP_CHUNK,), jnp.int32),
        pltpu.SMEM((ENTRY_WORDS * ENTRY_CHUNK,), jnp.int32),
        pltpu.SMEM((1,), jnp.int32),
    ]
    operands = [meta, steps, entries, consts, state]
    if n_dense:
        scratch += [pltpu.VMEM(dense.shape, DENSE_DTYPE),
                    pltpu.VMEM((n_pad, width), DENSE_DTYPE)]
        operands.append(dense)
    if key_rows:
        scratch += [pltpu.VMEM((key_rows, width), state.dtype),
                    pltpu.VMEM((SUBLANES, KEY_BLOCK + SUBLANES, width),
                               state.dtype)]
        operands.append(keys)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(d_pad // width,),
        in_specs=[hbm] * (len(operands) - 1),
        out_specs=hbm,
        scratch_shapes=scratch)
    need = vmem_bytes(n_pad, width, n_regs, itemsize, n_dense, key_rows)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(state.shape, state.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(max(need, 32 * 1024 * 1024),
                                 VMEM_CAP_BYTES)),
        interpret=interpret,
        name="plan_program",
    )(*operands)
