"""Blockwise low-precision codecs for optimizer state.

Three layouts, one module:

  * Flat INT8 (``quantize``/``dequantize``) — the Dettmers et al. (2022)
    dynamic-exponent codebook over 256-element blocks of the flattened
    array. Moved here from ``optim/quant8.py`` (which remains a shim); this
    is the storage layout of standalone 8-bit Adam and the numerical oracle
    for ``kernels/adam8bit_update.py``.

  * Flat INT4 (``quantize4``/``dequantize4``) — signed linear 15-level map
    (q/7 for q in -7..7, exact zero preserved) with per-block absmax, two
    codes packed per byte. This is the Q-GaLore projector storage format:
    0.5 B/elem + 4 B absmax per 256 elems ≈ 8× smaller than an fp32
    projector, and projectors tolerate the linear (non-dynamic) map because
    their entries are near-uniform O(1/√m) rotations, not heavy-tailed
    moments.

  * Axis-blocked INT8 (``quantize_axis``/``dequantize_axis``) — the layout
    the fused GaLore kernels consume: blocks of ``QBLOCK`` elements run
    along ONE trailing axis (the kernel's swept axis), so a column/row tile
    of the compact moment covers whole quantization blocks and the
    dequant→Adam→requant epilogue never crosses a block boundary mid-tile.
    Codes keep the logical (r, n)/(m, r) shape; scales shrink the blocked
    axis by QBLOCK. QBLOCK = 128 = the TPU lane width, so a scale row maps
    onto one lane-aligned vector per tile.

All quantize paths compute in f32 and are shape-polymorphic over leading
batch dims. Non-divisible tails are zero-padded before the absmax, which is
exactly what the in-kernel masking reproduces (see galore_fused.py).

The INT8 codecs run under the named scopes ``int8_quantize`` and
``int8_dequantize``, so every op they emit carries the scope in its HLO
``op_name`` under any caller's jit, grad or vmap: a profiler trace can tell
their device time from the rest of the optimizer's. Scopes are metadata and
change no instruction; the Pallas kernels' own epilogues are not in them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 256   # flat-codec block (bitsandbytes convention)
QBLOCK = 128  # axis-blocked codec block (TPU lane width)

# per-moment salts for the stochastic-rounding hash (distinct streams for M
# and V so the two moments of one element never share a coin flip)
SR_SALT_M = 0x5BD1E995
SR_SALT_V = 0xC2B2AE35


def _scoped(name: str):
    """Run the decorated function under ``jax.named_scope(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return scoped
    return wrap


# ---------------------------------------------------------------------------
# Codebooks
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def dynamic_codebook(signed: bool = True) -> np.ndarray:
    """256 sorted codebook values in [-1, 1] (signed) or [0, 1] (unsigned).

    Dynamic-exponent map (Dettmers et al., 2022): sign × power-of-10
    exponent × linear fraction — dense near zero where Adam moments live.
    """
    total_bits = 8
    sign_bits = 1 if signed else 0
    non_sign_bits = total_bits - sign_bits
    max_exp_bits = non_sign_bits - 1  # reserve indicator bit layout
    data = [0.0]
    for e in range(max_exp_bits):
        frac_items = 2 ** (non_sign_bits - 1 - max_exp_bits + e + 1)
        boundaries = np.linspace(0.1, 1.0, frac_items + 1)
        means = (boundaries[:-1] + boundaries[1:]) / 2.0
        vals = (10.0 ** (-(max_exp_bits - 1) + e)) * means
        data += vals.tolist()
        if signed:
            data += (-vals).tolist()
    data.append(1.0)
    if signed:
        data.append(-1.0)
    arr = np.sort(np.unique(np.asarray(data, np.float32)))
    # pad/trim to exactly 256 by inserting midpoints of the largest gaps
    while arr.size < 256:
        gaps = np.diff(arr)
        i = int(np.argmax(gaps))
        arr = np.insert(arr, i + 1, (arr[i] + arr[i + 1]) / 2.0)
    if arr.size > 256:
        keep = np.linspace(0, arr.size - 1, 256).round().astype(int)
        arr = arr[keep]
    return arr.astype(np.float32)


@functools.lru_cache(maxsize=None)
def int4_codebook() -> np.ndarray:
    """16 values: symmetric linear q/7 for q in -7..7; code 15 aliases +1.

    15 live levels keep an exact zero (a zeros-initialized projector
    round-trips to zeros) and symmetric ±1 endpoints; the spare 16th code
    decodes to +1 so any 4-bit pattern is valid."""
    levels = [(q - 7) / 7.0 for q in range(15)] + [1.0]
    return np.asarray(levels, np.float32)


# ---------------------------------------------------------------------------
# Flat INT8 (blocks of the flattened array)
# ---------------------------------------------------------------------------


def _pad_to_blocks(x: jnp.ndarray, block: int = BLOCK) -> tuple[jnp.ndarray, int]:
    flat = x.reshape(-1)
    pad = (-flat.size) % block
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(-1, block), pad


@_scoped("int8_quantize")
def quantize(x: jnp.ndarray, signed: bool = True):
    """x (any shape) -> (codes uint8 (nblocks, BLOCK), absmax (nblocks,) f32)."""
    book = jnp.asarray(dynamic_codebook(signed))
    blocks, _ = _pad_to_blocks(x.astype(jnp.float32))
    absmax = jnp.max(jnp.abs(blocks), axis=1) + 1e-12
    normed = blocks / absmax[:, None]
    mids = (book[:-1] + book[1:]) / 2.0
    codes = jnp.searchsorted(mids, normed).astype(jnp.uint8)
    return codes, absmax


@_scoped("int8_dequantize")
def dequantize(codes: jnp.ndarray, absmax: jnp.ndarray, shape, signed: bool = True):
    book = jnp.asarray(dynamic_codebook(signed))
    vals = book[codes.astype(jnp.int32)] * absmax[:, None]
    n = int(np.prod(shape))
    return vals.reshape(-1)[:n].reshape(shape)


def quant_state(x: jnp.ndarray, signed: bool = True) -> dict:
    codes, absmax = quantize(x, signed)
    return {"q": codes, "scale": absmax}


def dequant_state(st: dict, shape, signed: bool = True) -> jnp.ndarray:
    return dequantize(st["q"], st["scale"], shape, signed)


# ---------------------------------------------------------------------------
# Flat INT4 (packed two codes per byte) — projector storage
# ---------------------------------------------------------------------------


def quantize4(x: jnp.ndarray):
    """x (any shape) -> (packed uint8 (nblocks, BLOCK//2), absmax (nblocks,)).

    Even flat positions occupy the low nibble, odd the high nibble."""
    blocks, _ = _pad_to_blocks(x.astype(jnp.float32))
    absmax = jnp.max(jnp.abs(blocks), axis=1) + 1e-12
    normed = blocks / absmax[:, None]
    q = jnp.clip(jnp.round(normed * 7.0), -7, 7).astype(jnp.int32) + 7  # 0..14
    packed = (q[:, 0::2] | (q[:, 1::2] << 4)).astype(jnp.uint8)
    return packed, absmax


def dequantize4(packed: jnp.ndarray, absmax: jnp.ndarray, shape):
    book = jnp.asarray(int4_codebook())
    p = packed.astype(jnp.int32)
    codes = jnp.stack([p & 0xF, p >> 4], axis=-1).reshape(p.shape[0], -1)
    vals = book[codes] * absmax[:, None]
    n = int(np.prod(shape))
    return vals.reshape(-1)[:n].reshape(shape)


def quant4_state(x: jnp.ndarray) -> dict:
    packed, absmax = quantize4(x)
    return {"q": packed, "scale": absmax}


def dequant4_state(st: dict, shape) -> jnp.ndarray:
    return dequantize4(st["q"], st["scale"], shape)


# ---------------------------------------------------------------------------
# Axis-blocked INT8 — compact-moment storage for the fused kernels
# ---------------------------------------------------------------------------


def _blocked(x: jnp.ndarray, axis: int, block: int):
    """Pad `axis` to a block multiple and split it into (nb, block)."""
    n = x.shape[axis]
    nb = -(-n // block)
    pad = nb * block - n
    if pad:
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        x = jnp.pad(x, widths)
    return x.reshape(x.shape[:axis] + (nb, block) + x.shape[axis + 1:]), nb


def sr_uniform(idx: jnp.ndarray, count: jnp.ndarray, salt: int) -> jnp.ndarray:
    """Counter-based uniform in [0, 1) from (element index, step count, salt).

    A small stateless integer hash (Knuth multiply + murmur-style finalizer)
    shared bit-for-bit by the host requantizer and the Pallas epilogue: the
    same (idx, count, salt) triple always yields the same coin, so the
    kernel and the reference oracle produce identical stochastic codes.
    """
    idx = idx.astype(jnp.uint32)
    cnt = jnp.asarray(count).astype(jnp.uint32)
    x = idx * jnp.uint32(2654435761)
    x = x ^ (cnt * jnp.uint32(0x9E3779B9)) ^ jnp.uint32(salt & 0xFFFFFFFF)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    # x >> 8 < 2**24 fits int32 exactly; the TPU kernel lowering has no
    # unsigned-to-float conversion
    return (x >> 8).astype(jnp.int32).astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))


def _stochastic_codes(normed: jnp.ndarray, book: jnp.ndarray,
                      u: jnp.ndarray) -> jnp.ndarray:
    """Stochastic codebook rounding: round up with prob = fractional position.

    Exact codebook hits (including 0) stay deterministic because frac is 0
    there; normed == 1.0 lands on the top code because u < 1 always."""
    ge = jnp.sum(normed[..., None] >= book, axis=-1)  # codes with book <= x
    lo = jnp.clip(ge - 1, 0, book.shape[0] - 2)
    lo_val = book[lo]
    step = book[lo + 1] - lo_val
    frac = jnp.clip((normed - lo_val) / step, 0.0, 1.0)
    return (lo + (u < frac).astype(jnp.int32)).astype(jnp.uint8)


@_scoped("int8_quantize")
def quantize_axis(x: jnp.ndarray, *, axis: int = -1, block: int = QBLOCK,
                  signed: bool = True, stochastic: bool = False,
                  count=None, salt: int = 0):
    """Blockwise dynamic-INT8 along one trailing axis.

    x (..., n, ...) -> (codes uint8, same shape as x;
                        scales f32, `axis` shrunk to ceil(n/block)).
    The block axis matches the fused kernel's sweep axis (last for left-side
    compact moments (r, n), second-to-last for right-side (m, r)) so a
    kernel tile always covers whole blocks.

    With ``stochastic=True`` (Q-GaLore) codes round up with probability
    equal to the fractional position between the bracketing codebook values,
    keyed by a counter hash of (ravel index, ``count``, ``salt``) — unbiased
    in expectation and bitwise-reproducible across host and kernel."""
    axis = axis % x.ndim
    book = jnp.asarray(dynamic_codebook(signed))
    xf = x.astype(jnp.float32)
    blocks, _ = _blocked(xf, axis, block)
    absmax = jnp.max(jnp.abs(blocks), axis=axis + 1) + 1e-12
    normed = blocks / jnp.expand_dims(absmax, axis + 1)
    if stochastic:
        idx = jnp.arange(xf.size, dtype=jnp.uint32).reshape(xf.shape)
        bidx, _ = _blocked(idx, axis, block)
        u = sr_uniform(bidx, 0 if count is None else count, salt)
        codes = _stochastic_codes(normed, book, u)
    else:
        mids = (book[:-1] + book[1:]) / 2.0
        codes = jnp.searchsorted(mids, normed).astype(jnp.uint8)
    codes = codes.reshape(x.shape[:axis] + (-1,) + x.shape[axis + 1:])
    codes = jax.lax.slice_in_dim(codes, 0, x.shape[axis], axis=axis)
    return codes, absmax


@_scoped("int8_dequantize")
def dequantize_axis(codes: jnp.ndarray, scales: jnp.ndarray, *, axis: int = -1,
                    block: int = QBLOCK, signed: bool = True) -> jnp.ndarray:
    axis = axis % codes.ndim
    book = jnp.asarray(dynamic_codebook(signed))
    vals = book[codes.astype(jnp.int32)]
    scale = jnp.repeat(scales, block, axis=axis)
    scale = jax.lax.slice_in_dim(scale, 0, codes.shape[axis], axis=axis)
    return vals * scale


def quant_axis_state(x: jnp.ndarray, *, axis: int, signed: bool,
                     block: int = QBLOCK, stochastic: bool = False,
                     count=None, salt: int = 0) -> dict:
    codes, scales = quantize_axis(x, axis=axis, block=block, signed=signed,
                                  stochastic=stochastic, count=count, salt=salt)
    return {"q": codes, "scale": scales}


def dequant_axis_state(st: dict, *, axis: int, signed: bool,
                       block: int = QBLOCK) -> jnp.ndarray:
    return dequantize_axis(st["q"], st["scale"], axis=axis, block=block,
                           signed=signed)


# ---------------------------------------------------------------------------
# Axis-blocked packed INT4 — kernel-consumable projector storage
# ---------------------------------------------------------------------------


def quantize4_axis(x: jnp.ndarray, *, block: int = QBLOCK):
    """Packed INT4 projector codec, blocked along the kept axis (-2).

    x (..., m, r) -> (packed uint8 (..., m_pad//2, r),
                      scales f32 (..., ceil(m/block), r))
    with per-(block, column) absmax and the symmetric 15-level linear map of
    :func:`int4_codebook`. Packing is *split-half*: row i shares a byte with
    row i + m_pad//2 (low/high nibble), so the kernel unpack is a single
    ``concatenate([book[q & 0xF], book[q >> 4]], axis=-2)`` with no
    interleave relayout. Padded rows quantize to code 7 (exact 0)."""
    blocks, nb = _blocked(x.astype(jnp.float32), x.ndim - 2, block)
    absmax = jnp.max(jnp.abs(blocks), axis=-2) + 1e-12  # (..., nb, r)
    normed = blocks / absmax[..., :, None, :]
    q = jnp.clip(jnp.round(normed * 7.0), -7, 7).astype(jnp.int32) + 7
    q = q.reshape(x.shape[:-2] + (nb * block, x.shape[-1]))
    half = (nb * block) // 2
    lo = jax.lax.slice_in_dim(q, 0, half, axis=x.ndim - 2)
    hi = jax.lax.slice_in_dim(q, half, nb * block, axis=x.ndim - 2)
    packed = (lo | (hi << 4)).astype(jnp.uint8)
    return packed, absmax


def dequantize4_axis(packed: jnp.ndarray, scales: jnp.ndarray, short: int,
                     *, block: int = QBLOCK) -> jnp.ndarray:
    """Inverse of :func:`quantize4_axis`; `short` is the logical kept dim.

    Mirrors the in-kernel unpack op-for-op (gather → concat → scale in f32)
    so the fused kernel and this host path are bitwise identical."""
    book = jnp.asarray(int4_codebook())
    p = packed.astype(jnp.int32)
    vals = jnp.concatenate([book[p & 0xF], book[p >> 4]], axis=-2)
    nb = scales.shape[-2]
    blocks = vals.reshape(vals.shape[:-2] + (nb, block, vals.shape[-1]))
    blocks = blocks * scales[..., :, None, :]
    full = blocks.reshape(vals.shape)
    return jax.lax.slice_in_dim(full, 0, short, axis=full.ndim - 2)


def quant4_axis_state(x: jnp.ndarray, *, block: int = QBLOCK) -> dict:
    packed, scales = quantize4_axis(x, block=block)
    return {"q": packed, "scale": scales}


def dequant4_axis_state(st: dict, shape, *, block: int = QBLOCK) -> jnp.ndarray:
    return dequantize4_axis(st["q"], st["scale"], shape[-2], block=block)


def is_qstate(x) -> bool:
    """True for a quantized-leaf dict ({"q": codes, "scale": absmax})."""
    return isinstance(x, dict) and set(x.keys()) == {"q", "scale"}


def is_axis4_qstate(x) -> bool:
    """True for the axis-blocked packed-INT4 layout of quantize4_axis.

    Discriminates from the flat layout by rank: axis-blocked keeps matching
    ranks for codes and scales; the flat codec stores 2-D codes + 1-D
    scales."""
    return is_qstate(x) and x["q"].ndim == x["scale"].ndim and x["q"].ndim >= 2
