"""Mamba2 SSD: the chunk-local parts of the chunked scan as Pallas TPU
kernels, each with its own backward kernel.

Per chunk of Q positions and per head, with ``cs`` the within-chunk
cumulative sum of ``dt * A`` and ``h`` the state entering the chunk:

    state = sum_s exp(cs[Q-1] - cs[s]) dt[s] x[s] B[s]^T  (ssd_states_*)
    y[l]  = sum_{s <= l} (C[l] . B[s]) exp(cs[l] - cs[s]) dt[s] x[s]
            + exp(cs[l]) C[l] . h                         (ssd_intra_*)

``ssd_ref`` builds the decay matrix ``L = exp(segsum)``, the scores
``M = CB * L`` and, in its backward pass, ``dM`` and ``dL``, each
(B, C, H, Q, Q) float32 in HBM, and float32 copies of x in the layouts
its dots want.  Here the kernels form them per tile in VMEM from x in its
own dtype, and nothing of size Q x Q per head leaves the core.  The
recurrence over the chunks, padding and ``init_state`` stay the XLA code
of ``ssd_ref``, which takes these parts through its ``chunk_states`` and
``chunk_output`` arguments.

Grid (batch, chunk, group, head block).  A head block is ``kb`` heads of
one group, up to 512 lanes of x, worked in sub-blocks of 128 lanes (or of
one head, where P is a multiple of 128); in a sub-block each head's dots
run over the sub-block with the other heads' lanes zeroed, which costs
the matrix unit nothing at 128 lanes.  The kernels take ``dt`` in float32
and form ``x * dt`` in VMEM.  The output's backward kernel works in the
transposed orientation (rows s, columns l), so that every Q x Q dot is a
plain or an ``A . B^T`` product.  What a group's heads add up (dB, dC)
gathers in VMEM over the head-block axis (innermost, sequential) and is
written at its last step.

The output comes in x's dtype, and the gradients of x, B and C in
theirs.  The states come in the recurrence's order (C, B, H * P, N).  Per-head
vectors (``dt``, ``cs`` and their gradients) move as rows (B, C, H, Q), a
block of every head of a chunk, which the kernels transpose in VMEM for
the columns and from which each picks its heads' with a mask (a column
layout, or a block as narrow as a head block, would be padded to 128
lanes in HBM).  The segment sums, the exp, the mask and the products are
float32; the dots take ``MXU_DTYPE`` operands and accumulate in float32.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as PS

from repro.kernels import ref as _ref
from repro.kernels import resolve_interpret
from repro.sharding import current_rules

F32 = jnp.float32

MXU_DTYPE = jnp.bfloat16
"""Operand type of the kernels' dots, accumulated in float32: what the TPU's
XLA gives ``ssd_ref``'s float32 dots at the default precision (one
bfloat16 pass)."""

VMEM_BUDGET = 12 * 2**20
"""The most one grid step's blocks (twice, for the pipeline) and tiles may
take of the core's default scoped VMEM (16 MiB on a v5e)."""

TILE = 128
"""The Q x Q products run in TILE x TILE tiles; the causal mask zeroes
those above the diagonal, which are skipped."""

MAX_LANES = 512
"""The widest head block: more heads a step share its fixed work (the
transposes, the masks, ``C . B^T``) over more heads."""


def _pad(n: int, m: int) -> int:
    return -(-n // m) * m


def _sub_lanes(P: int) -> int:
    """Lanes of a sub-block: 128, or one head where P is wider."""
    return max(P, 128)


def heads_per_block(H: int, G: int, P: int, N: int, Q: int) -> Optional[int]:
    """Heads of one group per grid step, or None where the kernel does not
    take the shape: the chunk a multiple of 128, P a divisor or a multiple
    of 128, a whole number of sub-blocks in each group, and the tiles
    within VMEM."""
    if Q % TILE or H % G or (128 % P and P % 128):
        return None
    hs = _sub_lanes(P) // P
    fits = [kb for kb in range(hs, (H // G) + 1, hs)
            if (H // G) % kb == 0 and kb * P <= max(MAX_LANES, P)]
    if not fits:
        return None
    kb = fits[-1]
    W, Hl, Hs, Nl = kb * P, _pad(H, 128), _pad(H, 8), _pad(N, 128)
    blocks = (Q * W * (2 + 2 + 2)                # x, dy or y, dx
              + W * Nl * 4 * 2                   # states, their gradient
              + 4 * Q * 4 * Nl                   # B, C, dB, dC
              + 4 * Q * 4 * Hs)                  # dt, cs, ddt, dcs rows
    tiles = 4 * (Q * (10 * Q + 6 * W + 6 * Hl + 2 * Nl) + 2 * Hs * Q)
    return kb if 2 * blocks + tiles <= VMEM_BUDGET else None


def _lane_masks(hs: int, P: int):
    lane = lax.broadcasted_iota(jnp.int32, (1, hs * P), 1)
    return [(lane >= j * P) & (lane < (j + 1) * P) for j in range(hs)]


def _col(blk, i):
    """Column ``i`` (a traced index) of a (Q, H) block, as (Q, 1)."""
    lane = lax.broadcasted_iota(jnp.int32, (1, blk.shape[1]), 1)
    return jnp.sum(jnp.where(lane == i, blk, 0.0), axis=1, keepdims=True)


def _row(blk, i):
    """Row ``i`` of an (H, Q) block, as (1, Q)."""
    sub = lax.broadcasted_iota(jnp.int32, (blk.shape[0], 1), 0)
    return jnp.sum(jnp.where(sub == i, blk, 0.0), axis=0, keepdims=True)


def _spread(cols, heads, masks):
    """Each head's column of a (Q, H) block over its lanes of a sub-block."""
    return sum(jnp.where(m, _col(cols, i), 0.0) for i, m in zip(heads, masks))


def _dot(a, b, mxu, contract_b: int = 0):
    """a . b in ``mxu`` operands at the default precision, whatever the
    process-wide default (a float32 ``highest`` would ask Mosaic for float32
    passes over bfloat16 operands)."""
    return lax.dot_general(a.astype(mxu), b.astype(mxu),
                           (((1,), (contract_b,)), ((), ())),
                           precision=lax.Precision.DEFAULT,
                           preferred_element_type=F32)


def _dot_t(a, b, mxu):
    """a . b^T, contracting the lanes of both."""
    return _dot(a, b, mxu, contract_b=1)


def _sub_blocks(kb: int, P: int):
    """(lanes, the block's heads, their lane masks) of each sub-block, the
    heads counted from the step's first: group g, head block h."""
    L = _sub_lanes(P)
    hs = L // P
    h0 = (pl.program_id(2) * pl.num_programs(3) + pl.program_id(3)) * kb
    masks = _lane_masks(hs, P)
    return [(slice(t * L, (t + 1) * L),
             [h0 + t * hs + j for j in range(hs)], masks)
            for t in range(kb // hs)]


def _tiles(Q: int):
    """Static row or column slices of the 128-wide tiles of a chunk."""
    return [slice(k * TILE, (k + 1) * TILE) for k in range(Q // TILE)]


def _output_fwd_kernel(x_ref, hp_ref, dt_ref, cs_ref, b_ref, c_ref, y_ref,
                       *, kb: int, P: int, mxu):
    """Rows l, columns s, in 128 x 128 tiles; tiles with s past l are zero
    and skipped."""
    Q = x_ref.shape[2]
    cs = cs_ref[0, 0]                                    # (H, Q)
    cs_cols, dt_cols = cs.T, dt_ref[0, 0].T              # (Q, H)
    ecs_cols = jnp.exp(cs_cols)
    cm = c_ref[0, 0, 0]
    cb = _dot_t(cm, b_ref[0, 0, 0], mxu)                 # (l, s)
    inter = _dot_t(cm, hp_ref[0, 0], mxu)                # C[l] . h
    causal = (lax.broadcasted_iota(jnp.int32, (TILE, TILE), 0)
              >= lax.broadcasted_iota(jnp.int32, (TILE, TILE), 1))
    tiles = _tiles(Q)
    for lanes, heads, masks in _sub_blocks(kb, P):
        xdt = (x_ref[0, 0, :, lanes].astype(F32)
               * _spread(dt_cols, heads, masks))
        ecl = _spread(ecs_cols, heads, masks)
        per_head = [(_col(cs_cols, i), _row(cs, i), jnp.where(m, xdt, 0.0))
                    for i, m in zip(heads, masks)]
        for lt, l in enumerate(tiles):
            y = inter[l, lanes] * ecl[l]
            for col, row, xh in per_head:
                for st, s_ in enumerate(tiles[:lt + 1]):
                    seg = col[l] - row[:, s_]            # cs[l] - cs[s]
                    if st == lt:
                        seg = jnp.where(causal, seg, -jnp.inf)
                    y = y + _dot(cb[l, s_] * jnp.exp(seg), xh[s_], mxu)
            y_ref[0, 0, l, lanes] = y.astype(y_ref.dtype)


def _output_bwd_kernel(x_ref, hp_ref, dt_ref, cs_ref, b_ref, c_ref, dy_ref,
                       dx_ref, dhp_ref, ddt_ref, dcs_ref, db_ref, dc_ref,
                       dcb_scr, dci_scr, ddt_scr, dcs_scr, *, kb: int, P: int,
                       mxu):
    """Rows s, columns l, in 128 x 128 tiles; tiles with s past l are zero
    and skipped.  ``ddt_scr`` and ``dcs_scr`` gather the chunk's per-head
    columns (Q, H) over the (group, head block) steps, and ``dcs_ref`` its
    rows, into which the columns go at the last step; ``dcb_scr`` and
    ``dci_scr`` gather a group's dCB^T and the inter-chunk part of dC over
    its head blocks."""
    Q = x_ref.shape[2]
    L = _sub_lanes(P)
    g, hb = pl.program_id(2), pl.program_id(3)
    last = ((g == pl.num_programs(2) - 1)
            & (hb == pl.num_programs(3) - 1))

    @pl.when((g == 0) & (hb == 0))
    def _zero_heads():
        for r in (ddt_scr, dcs_scr, dcs_ref):
            r[...] = jnp.zeros(r.shape, F32)

    @pl.when(hb == 0)
    def _zero_group():
        for r in (dcb_scr, dci_scr):
            r[...] = jnp.zeros(r.shape, F32)

    cs = cs_ref[0, 0]
    cs_cols, dt_cols = cs.T, dt_ref[0, 0].T
    ecs_cols = jnp.exp(cs_cols)
    lane = lax.broadcasted_iota(jnp.int32, (1, cs_cols.shape[1]), 1)
    sub = lax.broadcasted_iota(jnp.int32, (cs.shape[0], 1), 0)
    bm, cm, hp = b_ref[0, 0, 0], c_ref[0, 0, 0], hp_ref[0, 0]
    cbt = _dot_t(bm, cm, mxu)                              # (s, l)
    inter = _dot_t(cm, hp, mxu)                            # C[l] . h
    anticausal = (lax.broadcasted_iota(jnp.int32, (TILE, TILE), 1)
                  >= lax.broadcasted_iota(jnp.int32, (TILE, TILE), 0))
    tiles = _tiles(Q)
    pairs = [(st, lt) for st in range(len(tiles))
             for lt in range(st, len(tiles))]
    dcbt = {p: dcb_scr[tiles[p[0]], tiles[p[1]]] for p in pairs}
    dci = dci_scr[...]
    for t, (lanes, heads, masks) in enumerate(_sub_blocks(kb, P)):
        x = x_ref[0, 0, :, lanes].astype(F32)
        dtl = _spread(dt_cols, heads, masks)
        xdt = x * dtl
        dy = dy_ref[0, 0, :, lanes].astype(F32)
        de = dy * _spread(ecs_cols, heads, masks)          # d(C[l] . h)
        gi = de * inter[:, lanes]                          # d cs, inter part
        rows = slice(t * L, (t + 1) * L)
        dci = dci + _dot(de, hp[rows], mxu)
        dhp_ref[0, 0, rows, :] = _dot(de.T, cm, mxu)
        dxdt = [jnp.zeros((TILE, L), F32) for _ in tiles]
        for i, m in zip(heads, masks):
            col, row = _col(cs_cols, i), _row(cs, i)
            dyj = jnp.where(m, dy, 0.0)
            sum_s = [jnp.zeros((1, TILE), F32) for _ in tiles]   # at l
            sum_l = [jnp.zeros((TILE, 1), F32) for _ in tiles]   # at s
            for st, lt in pairs:
                s_, l = tiles[st], tiles[lt]
                seg = row[:, l] - col[s_]                  # cs[l] - cs[s]
                if st == lt:
                    seg = jnp.where(anticausal, seg, -jnp.inf)
                decay = jnp.exp(seg)
                mt = cbt[s_, l] * decay
                dxdt[st] = dxdt[st] + _dot(mt, dyj[l], mxu)
                dmt = _dot_t(xdt[s_], dyj[l], mxu)         # dM^T (s, l)
                dcbt[st, lt] = dcbt[st, lt] + dmt * decay
                ds = dmt * mt
                sum_s[lt] = sum_s[lt] + jnp.sum(ds, axis=0, keepdims=True)
                sum_l[st] = sum_l[st] + jnp.sum(ds, axis=1, keepdims=True)
            # d cs[l] += sum_s dS[l, s];  d cs[s] -= sum_l dS[l, s]
            hsum = _head_sum(gi, m)
            for k, q in enumerate(tiles):
                dcs_ref[0, 0, :, q] = jnp.where(sub == i, sum_s[k],
                                                dcs_ref[0, 0, :, q])
                dcs_scr[q, :] = jnp.where(lane == i, hsum[q] - sum_l[k],
                                          dcs_scr[q, :])
        for k, q in enumerate(tiles):
            gx = dxdt[k] * x[q]
            for i, m in zip(heads, masks):
                ddt_scr[q, :] = jnp.where(lane == i, _head_sum(gx, m),
                                          ddt_scr[q, :])
            dx_ref[0, 0, q, lanes] = (dxdt[k] * dtl[q]).astype(dx_ref.dtype)
    for (st, lt), v in dcbt.items():
        dcb_scr[tiles[st], tiles[lt]] = v
    dci_scr[...] = dci

    @pl.when(hb == pl.num_programs(3) - 1)
    def _emit_group():
        d = dcb_scr[...]
        db_ref[0, 0, 0] = _dot(d, cm, mxu).astype(db_ref.dtype)  # dCB^T C
        dc_ref[0, 0, 0] = (_dot(d.T, bm, mxu)
                           + dci_scr[...]).astype(dc_ref.dtype)

    @pl.when(last)
    def _emit_heads():
        ddt_ref[0, 0] = ddt_scr[...].T
        dcs_ref[0, 0] = dcs_ref[0, 0] + dcs_scr[...].T


def _head_sum(a, m):
    """Sum over one head's lanes of a sub-block: (Q, L) -> (Q, 1)."""
    return jnp.sum(jnp.where(m, a, 0.0), axis=1, keepdims=True)


def _states_fwd_kernel(x_ref, dt_ref, cs_ref, b_ref, st_ref, *, kb: int,
                       P: int, mxu):
    """Each head's state at the chunk's end, from the chunk alone:
    sum_s exp(cs[end] - cs[s]) dt[s] x[s] B[s]^T, as (P, N) rows."""
    Q = x_ref.shape[2]
    L = _sub_lanes(P)
    cs_cols, dt_cols = cs_ref[0, 0].T, dt_ref[0, 0].T    # (Q, H)
    dec_cols = jnp.exp(cs_cols[Q - 1:, :] - cs_cols)      # decay to the end
    bm = b_ref[0, 0, 0]
    for t, (lanes, heads, masks) in enumerate(_sub_blocks(kb, P)):
        xw = (x_ref[0, 0, :, lanes].astype(F32)
              * _spread(dt_cols, heads, masks)
              * _spread(dec_cols, heads, masks))
        st_ref[0, 0, t * L:(t + 1) * L, :] = _dot(xw.T, bm, mxu)


def _states_bwd_kernel(x_ref, dt_ref, cs_ref, b_ref, dst_ref,
                       dx_ref, ddt_ref, dcs_ref, db_ref,
                       db_scr, ddt_scr, dcs_scr, *, kb: int, P: int, mxu):
    """As in ``_output_bwd_kernel``, the per-head columns gather over the
    (group, head block) steps and ``db_scr`` a group's dB over its head
    blocks."""
    Q = x_ref.shape[2]
    L = _sub_lanes(P)
    g, hb = pl.program_id(2), pl.program_id(3)

    @pl.when((g == 0) & (hb == 0))
    def _zero_heads():
        for r in (ddt_scr, dcs_scr):
            r[...] = jnp.zeros(r.shape, F32)

    @pl.when(hb == 0)
    def _zero_group():
        db_scr[...] = jnp.zeros(db_scr.shape, F32)

    cs_cols, dt_cols = cs_ref[0, 0].T, dt_ref[0, 0].T
    dec_cols = jnp.exp(cs_cols[Q - 1:, :] - cs_cols)
    lane = lax.broadcasted_iota(jnp.int32, (1, cs_cols.shape[1]), 1)
    end = lax.broadcasted_iota(jnp.int32, (Q, 1), 0) == Q - 1
    bm = b_ref[0, 0, 0]
    db, ddt_cols, dcs_cols = db_scr[...], ddt_scr[...], dcs_scr[...]
    for t, (lanes, heads, masks) in enumerate(_sub_blocks(kb, P)):
        x = x_ref[0, 0, :, lanes].astype(F32)
        dtl = _spread(dt_cols, heads, masks)
        decl = _spread(dec_cols, heads, masks)
        xdt = x * dtl
        dst = dst_ref[0, 0, t * L:(t + 1) * L, :]         # (L, N)
        dxw = _dot_t(bm, dst, mxu)                        # (Q, L)
        db = db + _dot(xdt * decl, dst, mxu)
        dxdt = dxw * decl
        gdec = dxw * xdt
        for i, m in zip(heads, masks):
            # decay = exp(cs[end] - cs[s])
            dd = _head_sum(gdec, m) * _col(dec_cols, i)
            dcs_cols = jnp.where(
                lane == i,
                jnp.where(end, jnp.sum(dd, axis=0, keepdims=True), 0.0) - dd,
                dcs_cols)
            ddt_cols = jnp.where(lane == i, _head_sum(dxdt * x, m), ddt_cols)
        dx_ref[0, 0, :, lanes] = (dxdt * dtl).astype(dx_ref.dtype)
    db_scr[...], ddt_scr[...], dcs_scr[...] = db, ddt_cols, dcs_cols

    @pl.when(hb == pl.num_programs(3) - 1)
    def _emit_group():
        db_ref[0, 0, 0] = db_scr[...].astype(db_ref.dtype)

    @pl.when((g == pl.num_programs(2) - 1) & (hb == pl.num_programs(3) - 1))
    def _emit_heads():
        ddt_ref[0, 0] = ddt_scr[...].T
        dcs_ref[0, 0] = dcs_scr[...].T


def _specs(Q: int, W: int, H: int, N: int, nb: int):
    """BlockSpecs of the x-like (B, C, Q, H*P) arrays (a head block's
    lanes), the states (C, B, H*P, N) (its rows), the per-head rows
    (B, C, H, Q) (every head of the chunk) and the groups' (B, C, G, Q, N).
    """
    return dict(
        x=pl.BlockSpec((1, 1, Q, W), lambda b, c, g, h: (b, c, 0, g * nb + h)),
        hp=pl.BlockSpec((1, 1, W, N),
                        lambda b, c, g, h: (c, b, g * nb + h, 0)),
        row=pl.BlockSpec((1, 1, H, Q), lambda b, c, g, h: (b, c, 0, 0)),
        grp=pl.BlockSpec((1, 1, 1, Q, N), lambda b, c, g, h: (b, c, g, 0, 0)),
    )


def _call(name, kernel, args, kinds_in, outs, scratch, **kw):
    """``pallas_call`` of ``kernel`` over the grid (batch, chunk, group,
    head block): ``kinds_in`` names each argument's layout (``_specs``),
    ``outs`` gives (layout, dtype) of each output; ``scratch`` the VMEM
    buffers' dims by name (``"QH"``: Q by the heads on this chip), with
    which the group and head-block steps run in order (they gather across
    steps)."""
    x, Bt = args[0], args[kinds_in.index("grp")]
    dims = x.shape + (Bt.shape[2], Bt.shape[4])
    with jax.named_scope(name):
        return _pallas_call(name, kernel, kinds_in, tuple(outs),
                            tuple(scratch), dims, **kw)(*args)


@functools.lru_cache(maxsize=None)
def _pallas_call(name, kernel, kinds_in, outs, scratch, dims, *, kb, P, mxu,
                 interpret, qq_dots):
    """One ``pallas_call`` per kernel and shape, so that its kernel is
    traced once however often the program calls it (the forward pass, its
    recomputation and the custom VJP's forward rule).  ``qq_dots`` counts
    the (Q, Q)-sized dots per head for the scheduler."""
    Bb, Cn, Q, HP, G, N = dims
    H = HP // P
    nb = H // kb // G
    s = _specs(Q, kb * P, H, N, nb)
    shape = dict(x=(Bb, Cn, Q, HP), hp=(Cn, Bb, HP, N), row=(Bb, Cn, H, Q),
                 grp=(Bb, Cn, G, Q, N))
    dim = dict(Q=Q, H=H, N=N)
    qq = Bb * Cn * H * Q * Q
    return pl.pallas_call(
        functools.partial(kernel, kb=kb, P=P, mxu=mxu),
        grid=(Bb, Cn, G, nb),
        in_specs=[s[k] for k in kinds_in],
        out_specs=[s[k] for k, _ in outs],
        out_shape=[jax.ShapeDtypeStruct(shape[k], d) for k, d in outs],
        scratch_shapes=[pltpu.VMEM(tuple(dim[d] for d in sh), F32)
                        for sh in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
            + (("arbitrary",) * 2 if scratch else ("parallel",) * 2)),
        cost_estimate=pl.CostEstimate(
            flops=2 * qq_dots * qq * (P + N), transcendentals=qq,
            bytes_accessed=Bb * Cn * Q * HP * 2 * (1 + len(outs))),
        interpret=interpret, name=name)


def _mesh_axes(H: int, G: int, P: int, B: int):
    """(batch axes, head axes) of the active rules' mesh for the SSD's x,
    or None where a kernel cannot run on each chip's own part: the head
    dim P split, or the heads split inside a group."""
    rules = current_rules()
    if rules is None:
        return (None, None)
    spec = rules.spec(("batch", None, "heads_ssm", "ssm_p"), (B, 1, H, P))
    size = lambda a: 1 if a is None else rules.axis_size(
        a if isinstance(a, tuple) else (a,))
    if size(spec[3]) > 1 or G % size(spec[2]):
        return None
    return spec[0], spec[2]


def _on_mesh(fn, mesh, axes, kinds_in, kinds_out):
    """Run ``fn`` on each chip's own rows (and head groups) under
    ``shard_map`` where there is a mesh of more than one chip."""
    if mesh is None:
        return fn
    bat, hd = axes
    spec = dict(x=PS(bat, None, None, hd), hp=PS(None, bat, hd, None),
                row=PS(bat, None, hd, None), grp=PS(bat, None, hd, None, None))
    return jax.shard_map(fn, mesh=mesh,
                         in_specs=tuple(spec[k] for k in kinds_in),
                         out_specs=tuple(spec[k] for k in kinds_out),
                         check_vma=False)


_OUT_IN = ("x", "hp", "row", "row", "grp", "grp")    # x, h, dt, cs, B, C
_ST_IN = ("x", "row", "row", "grp")                   # x, dt, cs, B


@functools.lru_cache(maxsize=None)
def _vjps(kb: int, P: int, mxu, interpret: bool, mesh, axes, dtype):
    """The kernels' custom VJPs, chunk states and chunk output, for one
    block size, operand type, interpret mode, placement on a mesh (None:
    one chip) and x's dtype."""
    kw = dict(kb=kb, P=P, mxu=mxu, interpret=interpret)

    def pair(name, fwd_kernel, bwd_kernel, kinds, out, bwd_scratch,
             qq_dots):
        fwd = _on_mesh(
            lambda *a: tuple(_call(f"{name}_fwd", fwd_kernel, a, kinds,
                                   [out], (), qq_dots=qq_dots[0], **kw)),
            mesh, axes, kinds, (out[0],))
        bwd = _on_mesh(
            lambda *a: tuple(_call(
                f"{name}_bwd", bwd_kernel, a, kinds + (out[0],),
                [(k, v.dtype) for k, v in zip(kinds, a)],
                bwd_scratch, qq_dots=qq_dots[1], **kw)),
            mesh, axes, kinds + (out[0],), kinds)

        @jax.custom_vjp
        def f(*args):
            return fwd(*args)[0]

        f.defvjp(lambda *args: (fwd(*args)[0], args),
                 lambda res, ct: tuple(bwd(*res, ct)))
        return f

    return (pair("ssd_states", _states_fwd_kernel, _states_bwd_kernel,
                 _ST_IN, ("hp", F32), ("QN", "QH", "QH"), (0, 0)),
            pair("ssd_intra", _output_fwd_kernel, _output_bwd_kernel,
                 _OUT_IN, ("x", dtype), ("QQ", "QN", "QH", "QH"), (1, 3)))


def _kernels(x, dA_cs, kb, mxu, interpret):
    Bb, C, Q, G, K = dA_cs.shape
    H, P = G * K, x.shape[-1]
    rules = current_rules()
    mesh = None if rules is None or rules.mesh.size == 1 else rules.mesh
    return _vjps(kb, P, np.dtype(mxu), resolve_interpret(interpret), mesh,
                 _mesh_axes(H, G, P, Bb), np.dtype(x.dtype))


def _operands(x, dt, dA_cs):
    """x, dt and the cumulative sums in the kernels' layouts."""
    Bb, C, Q, G, K = dA_cs.shape
    H = G * K
    rows = lambda a: a.astype(F32).reshape(Bb, C, Q, H).transpose(0, 1, 3, 2)
    return x.reshape(Bb, C, Q, H * x.shape[-1]), rows(dt), rows(dA_cs)


def _group(a, C, Q):
    """(B, Sp, G, N) -> (B, C, G, Q, N) in a's dtype: a float32 copy of
    the model's bfloat16 B or C would hold no more than the dots take,
    and XLA writes it from the convolution before them."""
    Bb, _, G, N = a.shape
    return a.reshape(Bb, C, Q, G, N).transpose(0, 1, 3, 2, 4)


def ssd_chunk_states(x, dt, dA_cs, Bm, *, kb: int, mxu=MXU_DTYPE,
                     interpret: Optional[bool] = None):
    """``ssd_ref``'s ``chunk_states`` from the kernels: (B, C, G, K, P, N)
    float32.  Arguments as ``ssd_chunk_output``'s."""
    Bb, C, Q, G, K = dA_cs.shape
    N = Bm.shape[-1]
    states, _ = _kernels(x, dA_cs, kb, mxu, interpret)
    st = states(*_operands(x, dt, dA_cs), _group(Bm, C, Q))
    return jnp.moveaxis(st.reshape(C, Bb, G, K, x.shape[-1], N), 0, 1)


def ssd_chunk_output(x, dt, dA_cs, Bm, Cm, h_prev, *, kb: int,
                     mxu=MXU_DTYPE, interpret: Optional[bool] = None):
    """``ssd_ref``'s ``chunk_output`` from the kernels.

    x: (B, Sp, H, P); dt: (B, Sp, H); Bm, Cm: (B, Sp, G, N), all padded
    to a whole number of chunks; dA_cs: (B, C, Q, G, K) float32, the
    within-chunk cumulative sums of dt * A; h_prev: (B, C, G, K, P, N)
    float32, the state entering each chunk.  Returns (B, C, Q, G, K, P) in
    x's dtype.  Differentiable in every argument."""
    Bb, C, Q, G, K = dA_cs.shape
    P, N = x.shape[-1], Bm.shape[-1]
    _, output = _kernels(x, dA_cs, kb, mxu, interpret)
    xs, dts, css = _operands(x, dt, dA_cs)
    y = output(
        xs, jnp.moveaxis(h_prev, 1, 0).reshape(C, Bb, G * K * P, N), dts,
        css, _group(Bm, C, Q), _group(Cm, C, Q))
    return y.reshape(Bb, C, Q, G, K, P)


def ssd_kernel_path(x, dt, A, Bm, Cm, *, chunk: int, kb: int,
                    init_state=None, return_state: bool = False,
                    mxu=MXU_DTYPE, interpret: Optional[bool] = None):
    """``ssd_ref`` with each chunk's state and output from the kernels."""
    kw = dict(kb=kb, mxu=mxu, interpret=interpret)
    return _ref.ssd_ref(
        x, dt, A, Bm, Cm, chunk=chunk, init_state=init_state,
        return_state=return_state,
        chunk_states=functools.partial(ssd_chunk_states, **kw),
        chunk_output=functools.partial(ssd_chunk_output, **kw))


def kernel_takes(x_shape, G: int, N: int, chunk: int) -> Optional[int]:
    """Heads per block where the kernel path takes an SSD of these shapes
    under the active sharding rules, else None."""
    B, _, H, P = x_shape
    kb = heads_per_block(H, G, P, N, chunk)
    if kb is None or _mesh_axes(H, G, P, B) is None:
        return None
    return kb
