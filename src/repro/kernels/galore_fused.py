"""Fused GaLore→Adam→back-project Pallas TPU kernel.

One `pallas_call` computes the entire GaLore-Adam leaf update (paper Alg. 2):

    R  = Pᵀ G                               (MXU, f32 accumulate)
    M' = β₁ M + (1-β₁) R                    (VPU, in VMEM)
    V' = β₂ V + (1-β₂) R²
    N̂  = (M'/c₁) / (√(V'/c₂) + ε)
    G̃  = α · P N̂                            (MXU)

The unfused sequence (`galore_project` → `lowrank_adam_update` →
`galore_project_back`) writes R to HBM, reads it back with M/V, writes N̂,
and reads N̂ plus a second copy of P — for a memory-bound op that traffic is
the step time. Here R and N̂ live only in the f32 VMEM accumulator and P is
read once; HBM sees exactly one read of {P, G, M, V} and one write of
{G̃, M', V'} per leaf (see EXPERIMENTS.md §Perf for the analytic accounting).

Tiling scheme
-------------
Grid = (L, ⌈n / bn⌉): a leading batch dimension over stacked layers/experts
(L = 1 for plain 2-D leaves) and a sweep over column tiles of the long side.
Per grid step the kernel holds in VMEM:

    P  (m, r)   — whole projector, index map is constant in j, so the Pallas
                  pipeline fetches it once per batch element and keeps it
                  resident across the column sweep;
    G  (m, bn)  — one gradient column tile;
    M,V (r, bn) — the matching compact-moment column tiles;
    accumulators — R/N̂ (r, bn) and G̃ (m, bn) f32 registers.

Both matmuls contract in one `dot_general` each (no k-loop): the projection
contracts the full m inside the tile, the back-projection the full r. This
is exactly the GaLore regime — P projects the SHORT side, so m = min(m, n)
and r ≪ m both fit comfortably on chip.

VMEM budget
-----------
`_vmem_bytes` counts one grid step as Mosaic allocates it: every blocked
operand twice (the pipeline double-buffers constant-index blocks such as P
too), the scratch buffers, and the body's f32 temporaries. `_pick_bn`
halves the tile from DEFAULT_BN until that fits VMEM_BUDGET, and every
call raises Mosaic's scoped limit (16 MiB by default; a v5e core has
128 MiB) to VMEM_LIMIT. At the paper's LLaMA-1B widths (short side 2048,
r = 512) every variant keeps the 512 tile. If even a 128 tile does not fit
(the projected side is tens of thousands of rows), a ValueError directs
callers to the unfused kernels.

Aliasing contract
-----------------
The moment inputs (and W, when applying) are donated and updated in place
(their HBM buffers become the outputs). Callers must treat them as
consumed — jit'd callers get this for free from XLA buffer donation; eager
callers must not reuse the inputs. Ragged (m, n, r) are safe: m and r are
spanned whole by every block, and last-tile padding on the swept axis only
ever produces out-of-bounds outputs, which Pallas discards.

dtypes: P/G accept f32 or bf16; fp32 moments are f32 (the optimizer state
of record); G̃ is emitted f32, W' in W's dtype.

Variants
--------
`_fused_epilogue_call` builds every variant from one kernel body over
(side × int8-moments × apply-weight × projector layout):

  * int8 moments (`galore_fused_adam8_step[_right]`): M/V arrive as uint8
    codes + per-block absmax in the axis-blocked layout of quant/codec.py
    (blocks of QBLOCK=128 along the swept axis, so a tile covers whole
    blocks). The kernel dequantizes in VMEM, runs the f32 Adam math, and
    requantizes — fp32 moments NEVER touch HBM, which is the paper's 8-bit
    GaLore configuration fused into the single-pass kernel. Codebook reads
    are 128-lane table gathers, and requantization is a binary search over
    the sorted midpoints; ragged tails are masked to zero so a partially
    valid quantization block sees exactly the zero padding the reference
    codec pads with.

  * int4 projector (a codec.quantize4_axis qstate P): the packed nibbles
    are unpacked into a VMEM scratch once per batch element.

  * weight apply (`*_apply_step[_right]`): the kernel additionally reads a
    W tile and emits W' = W + eta·(α P N̂ + wd·W) in W's dtype, aliased in
    place — the full-size f32 update write disappears from the step
    entirely (the launcher's lr/weight-decay chain is folded in via
    eta = -lr). The two-step emit path remains the numerics oracle.

  * fold (`adam8_axis_update`, project=False): no projector, R = G — the
    int8 Adam update of a full-shape leaf (embeddings, leaves GaLore does
    not project), its rows folded into the batch grid axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.galore_project import _batch
from repro.quant.codec import (
    QBLOCK,
    SR_SALT_M,
    SR_SALT_V,
    dynamic_codebook,
    int4_codebook,
    is_qstate,
    sr_uniform,
)

DEFAULT_BN = 512
# A v5e TensorCore has 128 MiB of VMEM, but Mosaic's default scoped limit is
# 16 MiB. At the paper's LLaMA-1B rank the resident projector alone is 4 MiB
# of f32 (2048×512) and Pallas double-buffers it, so every fused call raises
# the limit to VMEM_LIMIT and sizes its tile against VMEM_BUDGET, which
# leaves the compiler room for what `_vmem_bytes` does not model.
VMEM_LIMIT = 96 * 1024 * 1024
VMEM_BUDGET = 64 * 1024 * 1024
# the full-shape int8 fold (`adam8_axis_update`): its widest lane tile, and
# the leaf elements one grid step aims to cover (1 MiB of a bf16 gradient),
# so that the per-step overhead stays small against the tile's work
FOLD_MAX_TILE = 4096
FOLD_STEP_ELEMS = 1 << 19
_LANES = 128
# f32 matmuls in full f32 (multi-pass on the MXU): the TPU's default rounds
# f32 operands to bf16, and Adam's normalization N̂ = m / √v turns that
# rounding of R into O(1) errors wherever m' is near zero
_F32 = jax.lax.Precision.HIGHEST


def _vmem_bytes(short: int, r: int, tile: int, g_itemsize: int, *,
                side: str = "left", quant: bool = False,
                quant_p: bool = False, apply_w: bool = False,
                w_itemsize: int = 4, qblock: int = QBLOCK) -> int:
    """VMEM one grid step of the fused call occupies, counted the way Mosaic
    allocates it: every blocked operand twice (the pipeline double-buffers
    constant-index blocks such as P too), the unpacked-projector scratch,
    and the body's f32 temporaries (G and G̃ at (short, tile), about eight
    compact (r, tile) arrays). Minor dims pad to 128 lanes, second-minor
    dims to 8 sublanes."""
    pad = lambda x, k: -(-x // k) * k
    big = short * tile
    mom = r * tile
    blocks = big * g_itemsize + big * (w_itemsize if apply_w else 4)
    scratch = 0
    if apply_w:
        blocks += big * w_itemsize
    if quant:
        nbt = max(tile // qblock, 1)
        sblk = (pad(r, 8) * pad(nbt, _LANES) if side == "left"
                else pad(nbt, 8) * pad(r, _LANES))
        blocks += 4 * mom + 4 * 4 * sblk        # codes + scales, in and out
        scratch += 4 * mom                      # staged R / N̂
    else:
        blocks += 4 * 4 * mom                   # f32 M, V in and out
    if quant_p:
        m_pad = pad(short, QBLOCK)
        blocks += (m_pad // 2) * pad(r, _LANES) + 4 * (m_pad // QBLOCK) * pad(r, _LANES)
        scratch += 4 * m_pad * pad(r, _LANES)
    else:
        blocks += 4 * short * pad(r, _LANES)
    temps = 4 * (2 * big + 8 * mom)
    return 2 * blocks + scratch + temps


def _pick_bn(m: int, r: int, n: int, g_itemsize: int, bn0: int,
             **variant) -> int:
    """Largest tile of the swept (long) axis, ≤ bn0 and halving down to the
    128-lane floor, whose `_vmem_bytes` fits VMEM_BUDGET. `m` is the short
    (projected) side, `n` the swept one; `variant` holds the kernel flags
    of `_vmem_bytes`."""
    bn = min(bn0, n)
    while _vmem_bytes(m, r, bn, g_itemsize, **variant) > VMEM_BUDGET and bn > 128:
        bn //= 2
    if _vmem_bytes(m, r, min(bn, 128), g_itemsize, **variant) > VMEM_BUDGET:
        raise ValueError(
            f"galore_fused: P ({m}×{r}) + minimal tiles exceed VMEM budget "
            f"({VMEM_BUDGET} B); use the unfused galore_project path"
        )
    return bn


def fits_vmem(m: int, r: int, n: int, g_itemsize: int, bn0: int = None,
              **variant) -> bool:
    """True if the fused kernel's VMEM budget admits this leaf shape (the
    dispatch predicate — callers route to the unfused kernels otherwise).
    `m` is the short side, `n` the swept one."""
    try:
        _pick_bn(m, r, n, g_itemsize, bn0 or DEFAULT_BN, **variant)
        return True
    except ValueError:
        return False


def _table(values) -> jnp.ndarray:
    """A 256-entry lookup table as the (2, 128) lane layout `_lut` reads
    (entry e at [e // 128, e % 128])."""
    return jnp.asarray(values, jnp.float32).reshape(2, _LANES)


def _lut(tab, idx):
    """tab[idx] for a (2, 128) table and int32 indices in [0, 256).

    The TPU gathers within 128-lane rows only, so each 128-wide index chunk
    gathers from both table rows and selects on bit 7. Compiling for the
    chip needs idx's minor dim to be a multiple of 128 (kernels/ops.py pads
    a right-side call's rank lanes to one); interpret mode takes any width."""
    rows = idx.shape[0]
    parts = []
    for c0 in range(0, idx.shape[1], _LANES):
        ix = idx[:, c0:c0 + _LANES]
        lo = ix & (_LANES - 1)
        got = [jnp.take_along_axis(
            jnp.broadcast_to(tab[h:h + 1, :], (rows, _LANES)), lo, axis=1,
            mode="promise_in_bounds") for h in range(2)]
        parts.append(jnp.where(ix >= _LANES, got[1], got[0]))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _count_le(tab, x):
    """Number of entries of the sorted 256-entry table `tab` that are <= x,
    by an 8-step binary search of table lookups (0..256)."""
    lo = jnp.zeros(x.shape, jnp.int32)
    for step in (128, 64, 32, 16, 8, 4, 2, 1):
        lo = jnp.where(_lut(tab, lo + (step - 1)) <= x, lo + step, lo)
    return lo + (_lut(tab, lo) <= x).astype(jnp.int32)


def _int4_decode(q):
    """int4_codebook()[q] for int32 nibble codes, as a 16-way select chain
    (no width constraint, exact)."""
    out = jnp.zeros(q.shape, jnp.float32)
    for k, v in enumerate(int4_codebook().tolist()):
        out = jnp.where(q == k, jnp.float32(v), out)
    return out


def _epilogue_kernel(*refs, side, quant, quant_p, project, apply_w, out_dtype,
                     b1, b2, eps, alpha, wd, long_dim, tile, qblock, p_short,
                     stochastic):
    """One body for every fused kernel variant (side × int8 moments ×
    weight apply × projector layout).

    Ref order (inputs):  [Pq, Ps | P], G, [W],
                         (Mq, Ms, Vq, Vs | M, V), count (SMEM int32 (1,)),
                         coef (SMEM f32 (3,): c1, c2, eta),
                         [book_s, book_u, mids_s, mids_u]
    Ref order (outputs): out, (Mq', Ms', Vq', Vs' | M', V')
    Scratch:             [P (m_pad, r) f32] when quant_p,
                         [R / N̂ (compact tile) f32] when quant
    Array blocks carry a leading batch dim of 1; scale blocks carry two
    (batch, tile) — see `_fused_epilogue_call`. The bias corrections c1, c2
    and eta (the folded -lr) are runtime scalars computed by the caller:
    the step count changes them every step, and Mosaic has no powf.

    The short and rank dims are spanned whole by every block; only the long
    axis is swept, so garbage in the last tile's padding stays local to
    out-of-bounds columns (rows on the right), which Pallas drops. Int8
    moments are masked to zero there so a boundary quantization block's
    absmax sees the zero padding the reference codec uses.

    quant_p: P arrives as packed nibble codes (split-half layout of
    codec.quantize4_axis — row i shares a byte with row i + m_pad/2) plus
    per-(QBLOCK-block, column) absmax scales; the first grid step of each
    batch element unpacks them into the f32 scratch, which the sweep reuses.
    project=False: no P at all, R = G — full-shape 8-bit Adam
    (`adam8_axis_update`) with the moment shape equal to the gradient shape.
    stochastic: Q-GaLore stochastic rounding on the requant, keyed by a
    counter hash of (logical ravel index, step count, per-moment salt) that
    is bit-shared with codec.quantize_axis(stochastic=True).
    """
    it = iter(refs)
    if project and quant_p:
        pq_ref, ps_ref = next(it), next(it)
    elif project:
        p_ref = next(it)
    g_ref = next(it)
    w_ref = next(it) if apply_w else None
    if quant:
        mq_ref, ms_ref, vq_ref, vs_ref = next(it), next(it), next(it), next(it)
    else:
        m_ref, v_ref = next(it), next(it)
    count_ref, coef_ref = next(it), next(it)
    if quant:
        book_s, book_u = next(it)[...], next(it)[...]
        mids_s, mids_u = next(it)[...], next(it)[...]
    out_ref = next(it)
    if quant:
        mq_out, ms_out, vq_out, vs_out = next(it), next(it), next(it), next(it)
    else:
        m_out, v_out = next(it), next(it)
    p_scr = next(it) if project and quant_p else None
    nh_scr = next(it) if quant else None

    lane_ax = 1 if side == "left" else 0   # the swept (blocked) axis
    # grid position, read outside the loops below (interpret mode cannot
    # evaluate program_id inside a nested loop body)
    lb, j = pl.program_id(0), pl.program_id(1)

    g = g_ref[0].astype(jnp.float32)
    if project:
        if quant_p:
            half = pq_ref.shape[1]          # m_pad // 2 packed rows
            nbp = ps_ref.shape[1]

            @pl.when(j == 0)
            def _unpack():
                # 64 packed rows at a time: the low nibbles are rows
                # [64c, 64c+64) of P, the high nibbles rows half + [64c, ...);
                # each lies inside one QBLOCK-row scale block
                def body(c, carry):
                    r0 = pl.multiple_of(c * 64, 64)
                    q = pq_ref[0, pl.ds(r0, 64), :].astype(jnp.int32)
                    lo_s = ps_ref[0, pl.ds(c // 2, 1), :]
                    hi_s = ps_ref[0, pl.ds((nbp + c) // 2, 1), :]
                    p_scr[pl.ds(r0, 64), :] = _int4_decode(q & 0xF) * lo_s
                    p_scr[pl.ds(half + r0, 64), :] = _int4_decode(q >> 4) * hi_s
                    return carry

                jax.lax.fori_loop(0, half // 64, body, 0)

            p = p_scr[...] if p_short == p_scr.shape[0] else p_scr[:p_short, :]
        else:
            p = p_ref[0].astype(jnp.float32)
        if side == "left":
            # R = Pᵀ G (MXU, f32 accumulate): (r, tile)
            R = jax.lax.dot_general(
                p, g, dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=_F32,
            )
        else:
            # R = G P: (tile, r)
            R = jax.lax.dot_general(
                g, p, dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=_F32,
            )
    else:
        R = g  # full-shape Adam: the "compact" moment IS the gradient shape

    c1, c2 = coef_ref[0], coef_ref[1]

    def adam(m_old, v_old, r_):
        m_new = b1 * m_old + (1.0 - b1) * r_
        v_new = b2 * v_old + (1.0 - b2) * (r_ * r_)  # as ref.py rounds it
        return m_new, v_new

    def normalize(m_new, v_new):
        return (m_new / c1) / (jnp.sqrt(v_new / c2) + eps)

    if quant:
        # dequant → Adam → requant a chunk of rows at a time (a loop, so the
        # table-lookup code is emitted once): on the left 32 rows with every
        # quantization block of the tile along their lanes, on the right one
        # qblock-row block. R is staged in `nh_scr` and overwritten with N̂.
        nh_scr[...] = R
        n_mom = nh_scr.shape[0]
        if side == "left":
            rows = 32 if n_mom % 32 == 0 else n_mom
            nblk = tile // qblock
        else:
            rows, nblk = qblock, 1

        def lanes(x, k):   # the k-th quantization block of a left chunk
            return x if nblk == 1 else x[:, k * qblock:(k + 1) * qblock]

        def join(parts):
            return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)

        def chunk(c, carry):
            r0 = pl.multiple_of(c * rows, rows)
            sl = pl.ds(r0, rows)
            srow = sl if side == "left" else pl.ds(c, 1)

            def deq(codes_ref, scales_ref, book):
                vals = _lut(book, codes_ref[0, sl, :].astype(jnp.int32))
                sc = scales_ref[0, 0, srow, :]   # (rows, nblk) | (1, r)
                if side == "right":
                    return vals * sc
                return join([lanes(vals, k) * sc[:, k:k + 1]
                             for k in range(nblk)])

            m_new, v_new = adam(deq(mq_ref, ms_ref, book_s),
                                deq(vq_ref, vs_ref, book_u), nh_scr[sl, :])
            # the last tile's padding beyond `long_dim` holds garbage (Pallas
            # pads OOB input reads); zero the moments there so a boundary
            # quantization block's absmax sees the reference codec's zeros
            pos = jax.lax.broadcasted_iota(jnp.int32, m_new.shape, lane_ax) \
                + j * tile + (0 if side == "left" else r0)
            valid = pos < long_dim
            m_new = jnp.where(valid, m_new, 0.0)
            v_new = jnp.where(valid, v_new, 0.0)
            nh_scr[sl, :] = normalize(m_new, v_new)
            if stochastic:
                # unbiased rounding: pick the upper bracketing code with
                # probability = fractional position, coin shared bitwise
                # with codec.quantize_axis via the ravel index of the
                # LOGICAL (L, *mom) array (padded tail values are exactly
                # 0 — a codebook hit — so index collisions there are inert)
                row = jax.lax.broadcasted_iota(jnp.int32, m_new.shape, 0) + r0
                col = jax.lax.broadcasted_iota(jnp.int32, m_new.shape, 1)
                u32 = lambda x: jnp.asarray(x).astype(jnp.uint32)
                if side == "left":
                    idx = (u32(lb * n_mom) + u32(row)) * jnp.uint32(long_dim) \
                        + u32(col) + u32(j * tile)
                else:
                    idx = (u32(lb * long_dim) + u32(pos)) \
                        * jnp.uint32(m_new.shape[1]) + u32(col)

            def req(x, book, mids, salt, codes_out, scales_out):
                u = sr_uniform(idx, count_ref[0], salt) if stochastic else None
                codes = []
                for k in range(nblk):
                    xb = lanes(x, k)
                    absmax = jnp.max(jnp.abs(xb), axis=lane_ax,
                                     keepdims=True) + 1e-12
                    normed = xb / absmax
                    if stochastic:
                        lo = jnp.clip(_count_le(book, normed) - 1, 0, 254)
                        lo_val = _lut(book, lo)
                        frac = jnp.clip(
                            (normed - lo_val) / (_lut(book, lo + 1) - lo_val),
                            0.0, 1.0)
                        codes.append(lo + (lanes(u, k) < frac).astype(jnp.int32))
                    else:
                        # nearest codebook entry: count the midpoints <= value
                        codes.append(_count_le(mids, normed))
                    if side == "left":
                        scales_out[0, 0, srow, k:k + 1] = absmax
                    else:
                        scales_out[0, 0, srow, :] = absmax
                codes_out[0, sl, :] = join(codes).astype(jnp.uint8)

            req(m_new, book_s, mids_s, SR_SALT_M, mq_out, ms_out)
            req(v_new, book_u, mids_u, SR_SALT_V, vq_out, vs_out)
            return carry

        jax.lax.fori_loop(0, n_mom // rows if side == "left" else tile // rows,
                          chunk, 0)
        n_hat = nh_scr[...]
    else:
        m_new, v_new = adam(m_ref[0], v_ref[0], R)
        m_out[0], v_out[0] = m_new, v_new
        n_hat = normalize(m_new, v_new)

    if not project:
        gt = n_hat  # the bias-corrected update IS the output (no sandwich)
    elif side == "left":
        gt = alpha * jax.lax.dot_general(
            p, n_hat, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_F32,
        )
    else:
        gt = alpha * jax.lax.dot_general(
            n_hat, p, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_F32,
        )
    if apply_w:
        w = w_ref[0].astype(jnp.float32)
        gt = w + coef_ref[2] * (gt + wd * w)
    out_ref[0] = gt.astype(out_dtype)


def _fused_epilogue_call(side, quant, apply_w, P, G, W, moments, count, *,
                         b1, b2, eps, alpha, eta, wd, tile0, interpret,
                         quant_p=False, project=True, alias_moments=True,
                         qblock=QBLOCK, stochastic=False, out_dtype=None):
    """Build + launch one fused-kernel variant. `moments` is (Mq, Ms, Vq, Vs)
    when quant else (M, V); returns (out, *new_moments).

    quant_p: P is a codec.quantize4_axis qstate dict — packed codes +
    per-block scales go to the kernel whole-resident and dequantize in VMEM.
    project=False (P is None): no projection sandwich, R = G — full-shape
    8-bit Adam as a degenerate epilogue (mom shape == G shape; see
    `adam8_axis_update`).
    alias_moments: update the moments in place (False for eager callers
    that reuse their inputs).
    qblock: the moment quantization block (QBLOCK for the axis layouts,
    optim.quant8.BLOCK for the flat blocks).
    stochastic: stochastic-rounding requant (quant only).
    out_dtype: dtype of the emitted update (f32 by default, W's when
    applying).

    Int8 scales travel in a tile-major 4-D layout, (L, tiles, r, nbt) on the
    left and (L, tiles, nbt, r) on the right, so each grid step's scale
    block spans its array's two minor dims whole — the TPU lowering refuses
    a block whose minor dim (nbt = tile / qblock) is not lane-aligned."""
    m, n = G.shape[-2:]
    if project:
        Pq = P["q"] if quant_p else None
        r = (Pq if quant_p else P).shape[-1]
    else:
        assert not apply_w and not quant_p, "fold mode is update-only"
        r = m  # moments share G's shape; "left" layout with short == r == m
    short, long_dim = (m, n) if side == "left" else (n, m)
    m_pad = -(-short // QBLOCK) * QBLOCK
    if project and quant_p:
        nbp = m_pad // QBLOCK
        assert Pq.shape[-2:] == (m_pad // 2, r), (Pq.shape, short)
        assert P["scale"].shape[-2:] == (nbp, r), (P["scale"].shape, nbp, r)
        assert Pq.dtype == jnp.uint8
    elif project:
        assert P.shape[-2] == short, (P.shape, G.shape)
    mom_shape = (r, n) if side == "left" else (m, r)
    nb_total = -(-long_dim // qblock)
    if quant:
        Mq, Ms, Vq, Vs = moments
        scale_shape = (r, nb_total) if side == "left" else (nb_total, r)
        assert Mq.shape[-2:] == mom_shape and Vq.shape[-2:] == mom_shape, (
            Mq.shape, Vq.shape, mom_shape)
        assert Ms.shape[-2:] == scale_shape and Vs.shape[-2:] == scale_shape, (
            Ms.shape, Vs.shape, scale_shape)
        assert Mq.dtype == jnp.uint8 and Vq.dtype == jnp.uint8
    else:
        M, V = moments
        assert M.shape[-2:] == mom_shape and V.shape[-2:] == mom_shape, (
            M.shape, V.shape, mom_shape)
        assert M.dtype == jnp.float32 and V.dtype == jnp.float32

    if project:
        p_arrs = (Pq, P["scale"]) if quant_p else (P,)
    else:
        p_arrs = ()
    n_p = len(p_arrs)
    batched = [_batch(x) for x in p_arrs + (G,) + tuple(moments)
               + ((W,) if apply_w else ())]
    lead = batched[n_p][1]
    assert all(b[1] == lead for b in batched), [b[0].shape for b in batched]
    arrs = [b[0] for b in batched]
    Gb = arrs[n_p]
    mom_b = list(arrs[n_p + 1:n_p + 1 + len(moments)])
    Wb = arrs[-1] if apply_w else None
    L = Gb.shape[0]

    if project:
        tile = _pick_bn(short, r, long_dim, Gb.dtype.itemsize, tile0,
                        side=side, quant=quant, quant_p=quant_p,
                        apply_w=apply_w,
                        w_itemsize=W.dtype.itemsize if apply_w else 4,
                        qblock=qblock)
    else:
        tile = min(tile0, long_dim)
    if quant:
        # a tile must cover whole quantization blocks (the scale tile is the
        # code tile's blocked axis divided by qblock)
        tile = -(-tile // qblock) * qblock
    nbt = max(tile // qblock, 1)
    ntiles = pl.cdiv(long_dim, tile)
    grid = (L, ntiles)

    def scales_to_tiles(s):
        extra = ntiles * nbt - nb_total
        if side == "left":
            s = jnp.pad(s, ((0, 0), (0, 0), (0, extra)))
            return s.reshape(L, r, ntiles, nbt).transpose(0, 2, 1, 3)
        s = jnp.pad(s, ((0, 0), (0, extra), (0, 0)))
        return s.reshape(L, ntiles, nbt, r)

    def scales_from_tiles(s):
        if side == "left":
            return s.transpose(0, 2, 1, 3).reshape(L, r, -1)[:, :, :nb_total]
        return s.reshape(L, -1, r)[:, :nb_total]

    if quant:
        mom_b[1], mom_b[3] = scales_to_tiles(mom_b[1]), scales_to_tiles(mom_b[3])

    # blockspecs: the short + rank dims are spanned whole; only the long
    # axis is swept (column tiles on the left, row tiles on the right)
    if side == "left":
        g_spec = pl.BlockSpec((1, m, tile), lambda l, j: (l, 0, j))
        mom_spec = pl.BlockSpec((1, r, tile), lambda l, j: (l, 0, j))
        scale_spec = pl.BlockSpec((1, 1, r, nbt), lambda l, j: (l, j, 0, 0))
        scale_tiles = (L, ntiles, r, nbt)
    else:
        g_spec = pl.BlockSpec((1, tile, n), lambda l, j: (l, j, 0))
        mom_spec = pl.BlockSpec((1, tile, r), lambda l, j: (l, j, 0))
        scale_spec = pl.BlockSpec((1, 1, nbt, r), lambda l, j: (l, j, 0, 0))
        scale_tiles = (L, ntiles, nbt, r)
    whole = lambda shape: pl.BlockSpec(shape, lambda l, j: (0,) * len(shape))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    in_specs = []
    if project and quant_p:
        # packed codes + scales are whole-resident like the f32 P was
        in_specs += [
            pl.BlockSpec((1, m_pad // 2, r), lambda l, j: (l, 0, 0)),
            pl.BlockSpec((1, nbp, r), lambda l, j: (l, 0, 0)),
        ]
    elif project:
        in_specs.append(pl.BlockSpec((1, short, r), lambda l, j: (l, 0, 0)))
    in_specs.append(g_spec)
    operands = list(arrs[:n_p]) + [Gb]
    if apply_w:
        in_specs.append(g_spec)
        operands.append(Wb)
    if quant:
        in_specs += [mom_spec, scale_spec, mom_spec, scale_spec]
    else:
        in_specs += [mom_spec, mom_spec]
    operands += mom_b
    # step scalars: the bias corrections exactly as ref.lowrank_adam_update
    # computes them, and eta (the folded -lr)
    cnt = jnp.asarray(count, jnp.int32)
    cf = cnt.astype(jnp.float32)
    coef = jnp.stack([1 - b1 ** cf, 1 - b2 ** cf,
                      jnp.asarray(eta, jnp.float32)]).astype(jnp.float32)
    in_specs += [smem, smem]
    operands += [cnt.reshape(1), coef]
    if quant:
        book_s, book_u = dynamic_codebook(True), dynamic_codebook(False)
        mids = lambda b: np.append((b[:-1] + b[1:]) / 2.0, np.inf)
        in_specs += [whole((2, _LANES))] * 4
        operands += [_table(book_s), _table(book_u),
                     _table(mids(book_s)), _table(mids(book_u))]

    if apply_w:
        out_dtype = W.dtype
    elif out_dtype is None:
        out_dtype = jnp.float32
    out_shapes = [jax.ShapeDtypeStruct((L, m, n), out_dtype)]
    out_specs = [g_spec]
    if quant:
        codes = jax.ShapeDtypeStruct((L,) + mom_shape, jnp.uint8)
        scales = jax.ShapeDtypeStruct(scale_tiles, jnp.float32)
        out_shapes += [codes, scales, codes, scales]
        out_specs += [mom_spec, scale_spec, mom_spec, scale_spec]
    else:
        out_shapes += [jax.ShapeDtypeStruct((L,) + mom_shape, jnp.float32)] * 2
        out_specs += [mom_spec, mom_spec]

    # moments (and W, when applying) are donated and updated in place
    aliases = {}
    if alias_moments:
        mom_in_base = n_p + (2 if apply_w else 1)
        aliases = {mom_in_base + i: 1 + i for i in range(len(moments))}
    if apply_w:
        aliases[n_p + 1] = 0  # W → W'

    kernel = functools.partial(
        _epilogue_kernel, side=side, quant=quant, quant_p=quant_p,
        project=project, apply_w=apply_w, out_dtype=out_dtype, b1=b1, b2=b2,
        eps=eps, alpha=alpha, wd=wd, long_dim=long_dim, tile=tile,
        qblock=qblock, p_short=short, stochastic=stochastic,
    )
    outs = pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=tuple(out_specs),
        out_shape=tuple(out_shapes), input_output_aliases=aliases,
        scratch_shapes=(
            ([pltpu.VMEM((m_pad, r), jnp.float32)] if project and quant_p
             else [])
            + ([pltpu.VMEM((r, tile) if side == "left" else (tile, r),
                           jnp.float32)] if quant else [])),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(*operands)
    outs = list(outs)
    if quant:
        outs[2], outs[4] = scales_from_tiles(outs[2]), scales_from_tiles(outs[4])
    restore = lambda x: x.reshape(*lead, *x.shape[1:])
    return tuple(restore(o) for o in outs)


def galore_fused_adam_step(P, G, M, V, count, *, b1=0.9, b2=0.999, eps=1e-8,
                           alpha=1.0, bn=DEFAULT_BN, interpret: bool = False):
    """Fused left-side GaLore-Adam step.

    P (..., m, r), G (..., m, n), M/V (..., r, n) f32, count scalar int32.
    Leading dims (stacked layers / experts) are flattened into one batch grid
    axis, so an (L, E, m, n) leaf is a single `pallas_call`. Returns
    (G̃ (..., m, n) f32, M', V'); M/V are updated in place via
    input_output_aliases — treat the inputs as donated. P may be a
    packed-INT4 qstate dict (in-VMEM projector dequant)."""
    return _fused_epilogue_call(
        "left", False, False, P, G, None, (M, V), count,
        b1=b1, b2=b2, eps=eps, alpha=alpha, eta=0.0, wd=0.0, tile0=bn,
        quant_p=is_qstate(P), interpret=interpret)


def galore_fused_adam_step_right(P, G, M, V, count, *, b1=0.9, b2=0.999,
                                 eps=1e-8, alpha=1.0, bm=DEFAULT_BN,
                                 interpret: bool = False):
    """Fused right-side GaLore-Adam step: R = G P → Adam → G̃ = α N̂ Pᵀ, for
    leaves whose short side is the last dim (m > n; P is (..., n, r), M/V
    are (..., m, r) f32), with P resident across a sweep over row tiles of
    the long m axis. M/V are updated in place — treat them as donated."""
    return _fused_epilogue_call(
        "right", False, False, P, G, None, (M, V), count,
        b1=b1, b2=b2, eps=eps, alpha=alpha, eta=0.0, wd=0.0, tile0=bm,
        quant_p=is_qstate(P), interpret=interpret)


def galore_fused_adam8_step(P, G, Mq, Ms, Vq, Vs, count, *, b1=0.9, b2=0.999,
                            eps=1e-8, alpha=1.0, bn=DEFAULT_BN,
                            stochastic: bool = False,
                            interpret: bool = False):
    """Fused left-side GaLore step with INT8 moments: R = PᵀG → dequant M/V →
    Adam → requant → G̃ = α P N̂. Codes/scales use the axis-blocked layout
    (quant/codec.py, blocks along n); all four moment arrays are updated in
    place. Returns (G̃ f32, Mq', Ms', Vq', Vs').

    P may be a packed-INT4 qstate dict (codec.quantize4_axis) — the kernel
    then dequantizes the projector in VMEM (no f32 P in HBM)."""
    return _fused_epilogue_call(
        "left", True, False, P, G, None, (Mq, Ms, Vq, Vs), count,
        b1=b1, b2=b2, eps=eps, alpha=alpha, eta=0.0, wd=0.0, tile0=bn,
        quant_p=is_qstate(P), stochastic=stochastic, interpret=interpret)


def galore_fused_adam8_step_right(P, G, Mq, Ms, Vq, Vs, count, *, b1=0.9,
                                  b2=0.999, eps=1e-8, alpha=1.0, bm=DEFAULT_BN,
                                  stochastic: bool = False,
                                  interpret: bool = False):
    """Right-side INT8-moment variant: R = G P → Adam → G̃ = α N̂ Pᵀ, blocks
    along the swept m axis. P may be a packed-INT4 qstate dict."""
    return _fused_epilogue_call(
        "right", True, False, P, G, None, (Mq, Ms, Vq, Vs), count,
        b1=b1, b2=b2, eps=eps, alpha=alpha, eta=0.0, wd=0.0, tile0=bm,
        quant_p=is_qstate(P), stochastic=stochastic, interpret=interpret)


def galore_fused_adam_apply_step(P, G, W, M, V, count, *, b1=0.9, b2=0.999,
                                 eps=1e-8, alpha=1.0, eta=-1e-3, wd=0.0,
                                 bn=DEFAULT_BN, interpret: bool = False):
    """Left-side fused step with the weight update folded in:
    W' = W + eta·(α P N̂ + wd·W), emitted in W's dtype and aliased in place —
    no full-size f32 G̃ write. Returns (W', M', V'). P may be a packed-INT4
    qstate dict (in-kernel dequant)."""
    return _fused_epilogue_call(
        "left", False, True, P, G, W, (M, V), count,
        b1=b1, b2=b2, eps=eps, alpha=alpha, eta=eta, wd=wd, tile0=bn,
        quant_p=is_qstate(P), interpret=interpret)


def galore_fused_adam_apply_step_right(P, G, W, M, V, count, *, b1=0.9,
                                       b2=0.999, eps=1e-8, alpha=1.0,
                                       eta=-1e-3, wd=0.0, bm=DEFAULT_BN,
                                       interpret: bool = False):
    return _fused_epilogue_call(
        "right", False, True, P, G, W, (M, V), count,
        b1=b1, b2=b2, eps=eps, alpha=alpha, eta=eta, wd=wd, tile0=bm,
        quant_p=is_qstate(P), interpret=interpret)


def galore_fused_adam8_apply_step(P, G, W, Mq, Ms, Vq, Vs, count, *, b1=0.9,
                                  b2=0.999, eps=1e-8, alpha=1.0, eta=-1e-3,
                                  wd=0.0, bn=DEFAULT_BN,
                                  stochastic: bool = False,
                                  interpret: bool = False):
    """INT8 moments AND in-place weight apply: the full 8-bit GaLore hot
    path — HBM sees G, W, the uint8 moment codes, and (with a qstate P)
    the packed INT4 projector; nothing else."""
    return _fused_epilogue_call(
        "left", True, True, P, G, W, (Mq, Ms, Vq, Vs), count,
        b1=b1, b2=b2, eps=eps, alpha=alpha, eta=eta, wd=wd, tile0=bn,
        quant_p=is_qstate(P), stochastic=stochastic, interpret=interpret)


def galore_fused_adam8_apply_step_right(P, G, W, Mq, Ms, Vq, Vs, count, *,
                                        b1=0.9, b2=0.999, eps=1e-8, alpha=1.0,
                                        eta=-1e-3, wd=0.0, bm=DEFAULT_BN,
                                        stochastic: bool = False,
                                        interpret: bool = False):
    return _fused_epilogue_call(
        "right", True, True, P, G, W, (Mq, Ms, Vq, Vs), count,
        b1=b1, b2=b2, eps=eps, alpha=alpha, eta=eta, wd=wd, tile0=bm,
        quant_p=is_qstate(P), stochastic=stochastic, interpret=interpret)


def adam8_axis_update(G, Mq, Ms, Vq, Vs, count, *, b1=0.9, b2=0.999,
                      eps=1e-8, stochastic: bool = False, qblock: int = QBLOCK,
                      rows_per_step: int | None = None,
                      alias_moments: bool = True, interpret: bool = False):
    """Full-shape 8-bit Adam (no projection) as the epilogue's fold.

    G (..., rows, n); Mq/Vq uint8 codes of G's shape and Ms/Vs f32 scales
    (..., rows, ceil(n / qblock)): the layout of
    codec.quantize_axis(axis=-1). The leading dims and the rows fold into
    the batch grid axis as (L, tb, n) "left" tiles with r == tb, and the
    kernel sweeps n in lane tiles of whole quantization blocks: one tile
    where n <= FOLD_MAX_TILE (the scales then need no relayout), else
    FOLD_MAX_TILE with the last tile masked. The stochastic coin's ravel
    index (lb·tb + row)·n + col is the leaf's own, so stochastic codes
    equal codec.quantize_axis(stochastic=True)'s bit for bit.

    tb (`rows_per_step`) sets the work per grid step: by default the
    largest power of two, at least 32, whose tile holds at most
    FOLD_STEP_ELEMS elements, halved while it does not divide the rows,
    down to 32 (the chip loads uint8 codes in whole 8-row tiles, so a
    7-row stack of norm scales takes a 32-row step). Rows that tb does not
    divide are padded with zeros and sliced off: a zero row dequantizes to
    0, stays 0 and emits 0. The moments are updated in place unless
    `alias_moments` is False.
    Returns (update in G's dtype, Mq', Ms', Vq', Vs')."""
    shape = G.shape
    n = shape[-1]
    rows = int(np.prod(shape[:-1]))
    tile = min(n, FOLD_MAX_TILE)
    if rows_per_step is None:
        tb = 1 << (max(FOLD_STEP_ELEMS // tile, 32).bit_length() - 1)
        while rows % tb and tb > 32:
            tb //= 2
    else:
        tb = min(rows_per_step, rows)
    L = -(-rows // tb)
    pad = L * tb - rows

    def fold(x):
        x = x.reshape(rows, x.shape[-1])
        if pad:
            x = jnp.pad(x, ((0, pad), (0, 0)))
        return x.reshape(L, tb, x.shape[-1])

    outs = _fused_epilogue_call(
        "left", True, False, None, fold(G), None,
        tuple(fold(x) for x in (Mq, Ms, Vq, Vs)), count,
        b1=b1, b2=b2, eps=eps, alpha=1.0, eta=0.0, wd=0.0, tile0=tile,
        project=False, alias_moments=alias_moments, qblock=qblock,
        stochastic=stochastic, out_dtype=G.dtype, interpret=interpret)

    def unfold(x, like):
        x = x.reshape(L * tb, x.shape[-1])
        return (x[:rows] if pad else x).reshape(like.shape)

    return tuple(unfold(o, x) for o, x in zip(outs, (G, Mq, Ms, Vq, Vs)))
