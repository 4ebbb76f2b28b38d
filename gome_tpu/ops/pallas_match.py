"""Pallas TPU kernel for the batched match step (SURVEY §7 step 4).

What it buys over the XLA `scan x vmap` baseline (engine/batch.py): the scan
materializes the full book state — and every one of the ~60 elementwise
passes over it — to HBM on each of the T time steps. This kernel blocks the
symbol axis, loads one block's books into VMEM ONCE, applies all T ops with
the books resident on-chip, and writes the final state back once:
intermediate HBM traffic disappears and the T-step dependency chain runs
entirely out of VMEM.

Semantics are not re-implemented: the kernel body calls the SAME
`step_rows_impl` core the scan path's step_impl wraps, so the oracle-parity
tests that pin the step pin this kernel too. The kernel is pure data
movement + orchestration; matching math lives in exactly one place
(engine/step.py).

TPU layout discipline (Mosaic tiles the minor two dims as (8, 128) and only
allows unaligned dynamic offsets on the major dim):

  * book arrays ship as per-side [S, cap] rows (10 arrays) — the public
    [S, 2, cap] BookState is sliced/restacked OUTSIDE the kernel. A [2, cap]
    side axis inside would waste 4x on the size-2 sublane dim and need an
    offset-concat restack every step, which Mosaic cannot lower.
  * the 7 op fields ship packed in ONE [T, 8, S] int32 array (row 7 spare);
    each step reads the [8, B] slab at its (major-dim, unaligned-ok) time
    index and peels rows.
  * the 8 per-op scalar outputs come back the same way: one [T, 8, S] pack.
  * the 5 non-derivable per-op fill-record arrays come back time-leading as
    [T, K, S]; the step's [B, K] records are transposed in-VMEM so the lane
    dim stays the (dense) symbol block (fill_qty / taker_after are
    reconstructed outside the kernel — see _REC_FIELDS).
The host repacks to the public [S, T, ...] StepOutput shapes outside the
kernel — pure XLA transposes, off the hot dependency chain.

The compiled kernel is int32-only (Mosaic has no 64-bit lowering);
BookConfig dtype=int64 callers use the scan path. On TPU
`pallas_available()` gates the choice; everywhere else
`pallas_batch_step(..., interpret=True)` executes the same code path in
interpreter mode (used by the CPU test suite for parity).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..engine.book import (
    GRID_I32_FIELDS,
    BookConfig,
    BookState,
    DeviceOp,
    StepOutput,
)
from ..engine.step import _Side, step_rows_impl

# Only the 5 non-derivable record fields cross the kernel boundary:
# fill_qty == maker_prefill - maker_remaining and taker_after ==
# taker volume - cumsum(fill_qty) are reconstructed outside (less VMEM,
# fewer in-kernel transposes, less HBM).
_REC_FIELDS = (
    "fill_price", "maker_oid", "maker_uid",
    "maker_prefill", "maker_remaining",
)
_SCALAR_FIELDS = (
    "n_fills", "fill_overflow", "taker_remaining", "rested",
    "book_overflow", "cancel_found", "cancel_volume", "expired",
)
_OP_FIELDS = ("action", "side", "kind", "price", "volume", "oid", "uid")


def pallas_available(dtype=jnp.int32) -> bool:
    """True when the default backend can run the compiled kernel. Mosaic has
    no 64-bit vector lowering, so int64 books always take the scan path."""
    return jax.default_backend() == "tpu" and jnp.dtype(dtype).itemsize <= 4


def interpret_block_s(s: int) -> int:
    """Interpret-mode lane blocking (no Mosaic constraints): any divisor
    works; prefer the sublane width so CPU tests tile like the compiled
    kernel. The ONE policy for every interpret-mode caller."""
    return next(b for b in (8, 4, 2, 1) if s % b == 0)


def blockable_rows(s: int) -> int:
    """The smallest row count at or over `s` the compiled kernel can block
    (Mosaic's lane-dim rule, enforced in pallas_batch_step): a multiple of 8
    up to 256 (one sublane-aligned whole-axis block; s % 8 != 0 hits
    unsupported relayouts), of 128 above (128-row blocks). The row rule is
    written here and nowhere else: plan_block_s refuses every other count,
    and BatchEngine provisions the device's lane axis to this floor, so a
    venue states its lanes and still runs every full grid compiled."""
    step = 8 if s <= 256 else 128
    return -(-s // step) * step


def plan_block_s(s: int, cap: int = 256) -> tuple[int | None, str | None]:
    """The compiled kernel's lane-blocking policy, in ONE place. Returns
    (block_s, None), or (None, reason) when no blocking is valid and the
    caller gives way to the scan path:

      "unblockable_rows" — s is not a row count blockable_rows gives:
          blocks are 128-multiples, or one sublane-aligned whole-axis
          block for modest s;
      "tile_over_budget" — the legal block's resident book tiles
          (~10 x block x 2*cap x 4 B, in/out aliased at ~2x) do not fit
          the 6 MB share of Mosaic's 16 MB scoped-VMEM stack: cap=1024 at
          block 128 is a compile-time VMEM OOM."""
    if s != blockable_rows(s):
        return None, "unblockable_rows"
    block = 128 if s % 128 == 0 else s
    if 10 * block * 2 * cap * 4 > 6 << 20:
        return None, "tile_over_budget"
    return block, None


def default_block_s(s: int, cap: int = 256) -> int | None:
    """plan_block_s without the reason: the block, or None."""
    return plan_block_s(s, cap)[0]


def kernel_plan(
    rows: int, cap: int, dtype, interpret: bool = False
) -> tuple[int | None, bool, str | None]:
    """The dispatch decision for one kernel="pallas" grid of `rows` lanes
    at cap class `cap` — shared by every call site (engine.batch._step,
    parallel.mesh) so what EngineStats counts is what ran. Returns
    (block_s, interpret_mode, None) when the Pallas kernel runs, or
    (None, False, reason) when the grid gives way to the scan path.

    `interpret` is the caller's pallas_interpret flag (CPU tests, the
    chip_smoke rehearsal): where the compiled kernel cannot run at all it
    selects the Pallas interpreter instead of the give-way. The
    interpreter has no layout rule, so it blocks rows the compiled kernel
    could not; it keeps the VMEM budget, so a rehearsal gives way exactly
    where the chip would."""
    compiled = pallas_available(dtype)
    if not compiled and not interpret:
        wide = jnp.dtype(dtype).itemsize > 4
        return None, False, "int64_books" if wide else "no_tpu_backend"
    block, reason = plan_block_s(rows, cap)
    if reason == "unblockable_rows" and not compiled:
        block, reason = interpret_block_s(rows), None
    return block, block is not None and not compiled, reason


def _kernel(config: BookConfig, t_block: int, *refs):
    """refs: 12 book-in (5 buy rows, 5 sale rows, count, next_seq) +
    1 op-pack-in + 12 book-out + 5 record-out + 1 scalar-pack-out.
    See module docstring for layouts.

    The grid is (lane blocks, time blocks): the book blocks' index maps
    ignore the time-block index, so each lane block's books stay RESIDENT
    in VMEM across the whole time sweep (Pallas revisited-block semantics;
    time is the innermost grid dim), while op/record/scalar blocks page
    through t_block-deep windows — VMEM cost is O(t_block), not O(T), so
    a hot symbol can run thousands of ops deep in one kernel launch. At
    time block 0 the input books seed the output refs; afterwards the
    carry lives in the output refs."""
    (bb_p, bb_l, bb_s, bb_o, bb_u, sb_p, sb_l, sb_s, sb_o, sb_u,
     cnt, nsq, ops,
     ob_p, ob_l, ob_s, ob_o, ob_u, os_p, os_l, os_s, os_o, os_u,
     ocnt, onsq,
     fp, mo, mu, mp, mr, scal) = refs
    rec_refs = (fp, mo, mu, mp, mr)

    @pl.when(pl.program_id(1) == 0)
    def _seed():
        for dst, src in (
            (ob_p, bb_p), (ob_l, bb_l), (ob_s, bb_s), (ob_o, bb_o),
            (ob_u, bb_u), (os_p, sb_p), (os_l, sb_l), (os_s, sb_s),
            (os_o, sb_o), (os_u, sb_u), (ocnt, cnt), (onsq, nsq),
        ):
            dst[...] = src[...]

    buy = _Side(ob_p[...], ob_l[...], ob_s[...], ob_o[...], ob_u[...])
    sale = _Side(os_p[...], os_l[...], os_s[...], os_o[...], os_u[...])
    counts = ocnt[...]  # [B, 2]
    # Loop carries stay rank-2: Mosaic's layout inference crashes on rank-1
    # vectors carried through fori_loop (layout.h implicit-dim check); the
    # [B, 1] squeeze/unsqueeze inside the body is free.
    carry = (buy, sale, counts[:, 0:1], counts[:, 1:2], onsq[...])

    step = jax.vmap(
        lambda b, a, nb, ns, nq, o: step_rows_impl(config, b, a, nb, ns, nq, o)
    )

    def body(t, carry):
        buy, sale, nb, ns, nq = carry
        slab = ops[pl.ds(t, 1)][0]  # [8, B] in config.dtype
        # The pack rides in config.dtype (lossless for the value fields; the
        # three code fields are small ints) — casting the codes back to i32
        # keeps step semantics identical across dtypes.
        op = DeviceOp(
            **{
                f: (
                    slab[i].astype(jnp.int32)
                    if f in GRID_I32_FIELDS
                    else slab[i]
                )
                for i, f in enumerate(_OP_FIELDS)
            }
        )
        buy, sale, nb, ns, nq, out = step(
            buy, sale, nb[:, 0], ns[:, 0], nq[:, 0], op
        )
        # fill records: [B, K] -> transpose -> slot t of [T, K, B]
        for ref, f in zip(rec_refs, _REC_FIELDS):
            ref[pl.ds(t, 1)] = jnp.transpose(getattr(out, f))[None]
        # per-op scalars: one [8, B] slab in config.dtype, so int64
        # taker_remaining/cancel_volume survive the pack intact
        dt = config.dtype
        s = jnp.stack([getattr(out, f).astype(dt) for f in _SCALAR_FIELDS])
        scal[pl.ds(t, 1)] = s[None]
        return buy, sale, nb[:, None], ns[:, None], nq[:, None]

    buy, sale, nb, ns, nq = jax.lax.fori_loop(0, t_block, body, carry)
    for ref, v in zip((ob_p, ob_l, ob_s, ob_o, ob_u), buy):
        ref[...] = v
    for ref, v in zip((os_p, os_l, os_s, os_o, os_u), sale):
        ref[...] = v
    # Two static slice-stores, not a concat: Mosaic's vector concat rejects
    # tiny lane extents (offset mismatch at block_s == 1).
    ocnt[:, 0:1] = nb
    ocnt[:, 1:2] = ns
    onsq[...] = nq


@functools.partial(
    jax.jit,
    static_argnums=(0,),
    static_argnames=("block_s", "interpret", "block_t", "grid_kind"),
)
def pallas_batch_step(
    config: BookConfig,
    books: BookState,
    ops: DeviceOp,
    block_s: int = 128,
    interpret: bool = False,
    block_t: int | None = None,
    grid_kind: str = "full",
) -> tuple[BookState, StepOutput]:
    """Drop-in replacement for engine.batch.batch_step with identical
    semantics (books [S, ...], ops [S, T] -> books', outs [S, T, ...]).
    S must be a multiple of block_s (callers pad lanes; NOP rows are free),
    and the compiled path needs block_s to be a multiple of 128 (the packed
    op/record/scalar blocks put the symbol axis on the lane dim).

    block_t: time-block depth (must divide T; default min(T, 64)). Books
    stay VMEM-resident across the time sweep while op/record windows page
    in t_block-deep blocks, so VMEM cost is O(block_t) and deep time axes
    (hot-symbol dense grids, engine/batch.py) fit at any T.

    grid_kind: "full" (row == lane) or "dense" (gathered live lanes), for
    the kernel's name only: its device events in a profile read
    match_<kind>_r<rows>_t<depth>_c<cap class>, so a trace tells the grids
    of one frame apart.
    """
    s, t_len = ops.action.shape
    if block_t is None:
        # Largest divisor of T that fits the paged-block VMEM budget:
        # per time step the kernel pages op (8 rows) + 5 record (K rows
        # each) + scalar (8 rows) blocks of block_s lanes, double-buffered
        # by the pipeline. Mosaic's scoped-VMEM stack is 16 MB and the
        # resident book tiles take ~10*block_s*2*cap*4 (in+out), so give
        # the paged blocks ~5 MB. (Found the hard way: cap=256 K=16
        # block_s=128 at block_t=64 allocates 17.5 MB and fails to
        # compile.)
        per_t = (
            block_s
            * (8 + 5 * config.max_fills + 8)
            * jnp.dtype(config.dtype).itemsize
            * 2
        )
        budget_t = max(int((5 << 20) // per_t), 1)
        block_t = min(t_len, 64, budget_t)
        while t_len % block_t:
            block_t -= 1
    if s % block_s != 0:
        raise ValueError(f"S={s} not a multiple of block_s={block_s}")
    if t_len % block_t != 0:
        raise ValueError(f"T={t_len} not a multiple of block_t={block_t}")
    if not interpret and not (
        block_s % 128 == 0 or (block_s == s and block_s % 8 == 0)
    ):
        # Packed op/record/scalar blocks put the symbol axis on the lane
        # dim; Mosaic requires lane-dim blocks to be 128-multiples unless
        # the block spans the full axis — and sub-sublane blocks (B % 8
        # != 0) hit unsupported pad/concat relayouts in the book rows.
        raise ValueError(
            f"compiled kernel needs block_s % 128 == 0, or block_s == S "
            f"with S % 8 == 0 (got block_s={block_s}, S={s})"
        )
    cap = config.cap
    k = config.max_fills
    dt = jnp.dtype(config.dtype)
    sq = jnp.dtype(config.seq_dtype)
    if not interpret and (dt.itemsize > 4 or sq.itemsize > 4):
        raise ValueError(
            "compiled pallas kernel is int32-only (no Mosaic 64-bit "
            "lowering); use the scan path (or interpret=True) for int64"
        )
    grid = (s // block_s, t_len // block_t)

    def bspec(*shape):
        # Symbol-major blocks: block i covers rows [i*block_s, ...) and the
        # full extent of every trailing axis. The time-block index j is
        # IGNORED — time is the innermost grid dim, so the block is
        # revisited and stays VMEM-resident across the whole time sweep.
        nd = len(shape)
        return pl.BlockSpec(
            (block_s,) + shape, lambda i, j, _nd=nd: (i,) + (0,) * _nd
        )

    def tspec(mid):
        # Time-paged blocks [block_t, mid, block_s] at (time block j, lane
        # block i): dynamic per-step access lands on the major dim; the
        # symbol block rides the lane dim; only a block_t-deep window is
        # resident at a time.
        return pl.BlockSpec(
            (block_t, mid, block_s), lambda i, j: (j, 0, i)
        )

    row = lambda dtype: jax.ShapeDtypeStruct((s, cap), dtype)
    book_specs = [bspec(cap)] * 10 + [bspec(2), bspec(1)]
    book_shape = (
        [row(dt), row(dt), row(sq), row(dt), row(dt)] * 2
        + [
            jax.ShapeDtypeStruct((s, 2), jnp.int32),
            jax.ShapeDtypeStruct((s, 1), sq),
        ]
    )
    in_specs = book_specs + [tspec(8)]
    out_specs = book_specs + [tspec(k)] * 5 + [tspec(8)]
    out_shape = (
        book_shape
        + [jax.ShapeDtypeStruct((t_len, k, s), dt)] * 5
        + [jax.ShapeDtypeStruct((t_len, 8, s), dt)]  # scalar pack
    )
    aliases = {i: i for i in range(12)}

    op_pack = jnp.stack(
        [jnp.transpose(getattr(ops, f).astype(dt)) for f in _OP_FIELDS]
        + [jnp.zeros((t_len, s), dt)],
        axis=1,
    )  # [T, 8, S] in config.dtype (lossless for every field)

    rows_in = [
        getattr(books, f)[:, side]
        for side in (0, 1)
        for f in ("price", "lots", "seq", "oid", "uid")
    ]

    call = pl.pallas_call(
        functools.partial(_kernel, config, block_t),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
        name=f"match_{grid_kind}_r{s}_t{t_len}_c{cap}",
    )
    call_args = (*rows_in, books.count, books.next_seq[:, None], op_pack)
    if interpret:
        outs = call(*call_args)
    else:
        # Trace the compiled kernel with x64 promotion off regardless of the
        # global flag: every input is concretely 32-bit, but with x64 on,
        # Python-int literals inside the kernel promote to int64 and send
        # Mosaic's convert_element_type lowering into infinite recursion.
        with jax.enable_x64(False):
            outs = call(*call_args)
    (ob_p, ob_l, ob_s, ob_o, ob_u, os_p, os_l, os_s, os_o, os_u,
     ocnt, onsq, fp, mo, mu, mp, mr, scal) = outs

    pair = lambda b, a: jnp.stack([b, a], axis=1)  # [S, cap] x2 -> [S, 2, cap]
    new_books = BookState(
        price=pair(ob_p, os_p),
        lots=pair(ob_l, os_l),
        seq=pair(ob_s, os_s),
        oid=pair(ob_o, os_o),
        uid=pair(ob_u, os_u),
        count=ocnt,
        next_seq=onsq[:, 0],
    )
    sca = jnp.transpose(scal, (2, 0, 1))  # [T, 8, S] -> [S, T, 8]
    fields = {
        f: jnp.transpose(r, (2, 0, 1))  # [T, K, S] -> [S, T, K]
        for f, r in zip(_REC_FIELDS, (fp, mo, mu, mp, mr))
    }
    for i, f in enumerate(_SCALAR_FIELDS):
        want = dt if f in ("taker_remaining", "cancel_volume") else jnp.int32
        fields[f] = sca[..., i].astype(want)
    # Derived record fields (post-kernel XLA; see _REC_FIELDS note). Both
    # are exactly the step's definitions: qty = maker lots consumed;
    # taker_after = taker volume minus the inclusive fill prefix, reported
    # only on slots that filled.
    qty = fields["maker_prefill"] - fields["maker_remaining"]  # [S, T, K]
    fields["fill_qty"] = qty
    cum = jnp.cumsum(qty, axis=-1)
    vol = ops.volume.astype(dt)[:, :, None]
    fields["taker_after"] = jnp.where(qty > 0, vol - cum, 0)
    out = StepOutput(**fields)
    return new_books, out
