"""Public jit'd kernel API — dispatches Pallas (TPU) vs pure-jnp reference.

`use_pallas=None` auto-selects: Pallas on TPU backends, reference elsewhere.
Tests pass use_pallas=True + interpret=True to execute the kernel bodies in
Python on CPU against the ref.py oracles.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from repro.kernels import adam8bit_update as adam8bit_k
from repro.kernels import galore_fused as galore_fused_k
from repro.kernels import galore_project as galore_k
from repro.kernels import ref
from repro.kernels import rmsnorm as rmsnorm_k
from repro.optim.quant8 import dynamic_codebook
from repro.quant import codec
from repro.utils import active_rules


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(use_pallas):
    return _on_tpu() if use_pallas is None else use_pallas


def _p_rank(P) -> int:
    """Rank of a projector passed either as f32/bf16 array or as the packed
    axis-blocked INT4 qstate dict (codec.quantize4_axis)."""
    return (P["q"] if codec.is_qstate(P) else P).shape[-1]


def _pallas(kernel, *args, **kw):
    """Call a Pallas kernel wrapper. Inside a multi-device mesh context the
    call runs under `jax.shard_map` with every operand replicated: GSPMD
    cannot partition a Mosaic kernel, so each device runs the whole call on
    gathered operands. The fused GaLore updates go through `_fused_update`,
    which keeps GaLore-ZeRO state on its owners."""
    rules = active_rules()
    if rules is None or rules.mesh.size == 1:
        return kernel(*args, **kw)
    rep = PartitionSpec()
    return jax.shard_map(functools.partial(kernel, **kw), mesh=rules.mesh,
                         in_specs=(rep,) * len(args), out_specs=rep,
                         check_vma=False)(*args)


_LANES = 128
_routes: list | None = None


@contextlib.contextmanager
def record_routes():
    """Collect `(side, G shape, route)` for every fused GaLore leaf update
    (`route` as `fused_path` names it) and `("full", G shape, route)` for
    every full-shape int8 Adam leaf (`adam8_full_step`: "fused" or
    "reference") traced inside the block, in trace order — what the
    traced program really dispatched."""
    global _routes
    prev, _routes = _routes, []
    try:
        yield _routes
    finally:
        _routes = prev


def _pad_lanes(side: str, quant: bool, interpret: bool) -> bool:
    """On the chip the in-kernel int8 codebook lookups gather within
    128-lane rows; a right-side int8 call has the rank on the lanes, so its
    rank is padded to a multiple of 128 (interpret mode takes any)."""
    return quant and side == "right" and not interpret


def fused_path(side: str, P, G, *, quant: bool = False, W=None,
               use_pallas=None, interpret: bool = False) -> str:
    """Which implementation a fused GaLore leaf update dispatches to:

    "fused"     — one Pallas kernel (galore_fused.py);
    "composed"  — the tiled project / back-project kernels around a jnp
                  Adam (fp32 emit variants whose P does not fit VMEM);
    "reference" — kernels/ref.py in plain XLA (no Pallas backend selected,
                  or a quantized / weight-apply variant whose P does not
                  fit VMEM).

    `side` is the plan's projection side, `quant` selects the int8-moment
    variants and `W` the weight-apply ones."""
    if not _resolve(use_pallas):
        return "reference"
    m, n = G.shape[-2:]
    short, long_dim = (m, n) if side == "left" else (n, m)
    r = _p_rank(P)
    if _pad_lanes(side, quant, interpret):
        r = -(-r // _LANES) * _LANES
    if galore_fused_k.fits_vmem(
            short, r, long_dim, G.dtype.itemsize, side=side, quant=quant,
            quant_p=codec.is_qstate(P), apply_w=W is not None,
            w_itemsize=4 if W is None else W.dtype.itemsize):
        return "fused"
    return "reference" if quant or W is not None else "composed"


def _p_plain(P, short: int):
    """Dense view of P for the reference / composed fallback paths; the
    Pallas epilogue consumes the packed dict directly instead."""
    if codec.is_qstate(P):
        return codec.dequantize4_axis(P["q"], P["scale"], short)
    return P


def _fused_kernel(kernel, side, P, G, extra, moments, count, *, quant,
                  zero, interpret, **kw):
    """Run one fused epilogue kernel: `kernel(P, G, *extra, *moments,
    count)` → (G̃ or W', *moments').

    A right-side int8 call on the chip pads P's and the moments' rank lanes
    with zeros (zero scales: the padded moments dequantize to 0, stay 0
    through Adam and give N̂ = 0, so they add nothing to G̃) and slices the
    new moments back.

    Inside a multi-device mesh context the kernel runs under
    `jax.shard_map` (GSPMD cannot partition a Mosaic call). With `zero`
    (GaLore-ZeRO, emit variants) the rank dim of P and of the moments is
    split over the ownership axes: each replica runs the kernel on its own
    rank block — R_s = P_sᵀG, Adam on the moments it owns, α P_s N̂_s — and
    a psum over those axes sums the partial back-projections into G̃. Every
    other dim is replicated for the call: FSDP/TP-sharded G and W are
    gathered for it, and so is the state of the weight-apply variants,
    whose W' cannot be summed. (Under ZeRO the stochastic-rounding coin is
    drawn per rank block, not from the whole array's ravel index.)"""
    mom_dim = -2 if side == "left" else -1

    def call(P, G, extra, moments, count):
        r = _p_rank(P)
        pad = -r % _LANES if _pad_lanes(side, quant, interpret) else 0
        if pad:
            widen = lambda x: jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
            P, moments = jax.tree_util.tree_map(widen, (P, moments))
        out = kernel(P, G, *extra, *moments, count, interpret=interpret, **kw)
        return (out[0], *(x[..., :r] for x in out[1:])) if pad else tuple(out)

    rules = active_rules()
    if rules is None or rules.mesh.size == 1:
        return call(P, G, extra, moments, count)
    owners = (rules.spec_for(("zero",), (_p_rank(P),))[0]
              if zero and not extra else None)

    def spec(x, dim):
        s = [None] * x.ndim
        s[dim] = owners
        return PartitionSpec(*s)

    rep = PartitionSpec()
    p_spec = jax.tree_util.tree_map(lambda x: spec(x, -1), P)
    m_specs = tuple(spec(x, mom_dim) for x in moments)

    def body(P, G, extra, moments, count):
        out = call(P, G, extra, moments, count)
        if owners is None:
            return out
        return (jax.lax.psum(out[0], owners), *out[1:])

    return jax.shard_map(
        body, mesh=rules.mesh,
        in_specs=(p_spec, rep, (rep,) * len(extra), m_specs, rep),
        out_specs=(rep, *m_specs), check_vma=False,
    )(P, G, tuple(extra), tuple(moments), count)


def _fused_update(side, kernel, reference, P, G, W, moments, count, *,
                  quant, zero, use_pallas, interpret, **kw):
    """Dispatch one fused GaLore leaf update along `fused_path` (recorded
    under `record_routes`): the Pallas kernel, the composed tiled kernels
    (fp32 emit variants), or the kernels/ref.py oracle."""
    path = fused_path(side, P, G, quant=quant, W=W, use_pallas=use_pallas,
                      interpret=interpret)
    if _routes is not None:
        _routes.append((side, tuple(G.shape), path))
    extra = () if W is None else (W,)
    if path == "fused":
        return _fused_kernel(kernel, side, P, G, extra, moments, count,
                             quant=quant, zero=zero, interpret=interpret, **kw)
    P = _p_plain(P, G.shape[-2] if side == "left" else G.shape[-1])
    if path == "reference":
        return reference(P, G, *extra, *moments, count, **kw)
    # P too large for VMEM residency — compose the tiled kernels (on
    # transposed views on the right)
    M, V = moments
    sw = (lambda x: x) if side == "left" else (lambda x: jnp.swapaxes(x, -1, -2))
    R = _pallas(galore_k.galore_project, P, sw(G), interpret=interpret)
    N, M_t, V_t = ref.lowrank_adam_update(R, sw(M), sw(V), count, kw["b1"],
                                          kw["b2"], kw["eps"])
    upd = _pallas(galore_k.galore_project_back, P, N, kw["alpha"],
                  interpret=interpret)
    return sw(upd), sw(M_t), sw(V_t)


def galore_project(P, G, *, use_pallas=None, interpret=False):
    """R = Pᵀ G. Leading batch dims (stacked layers/experts) run as one
    batched-grid kernel launch."""
    if _resolve(use_pallas):
        return _pallas(galore_k.galore_project, P, G, interpret=interpret)
    return ref.galore_project(P, G)


def galore_project_back(P, N, alpha: float, *, use_pallas=None, interpret=False):
    """G̃ = α P N. Leading batch dims run as one batched-grid kernel launch."""
    if _resolve(use_pallas):
        return _pallas(galore_k.galore_project_back, P, N, alpha,
                       interpret=interpret)
    return ref.galore_project_back(P, N, alpha)


def galore_fused_adam_step(P, G, M, V, count, *, b1=0.9, b2=0.999, eps=1e-8,
                           alpha=1.0, zero=False, use_pallas=None,
                           interpret=False):
    """Entire GaLore-Adam leaf update in one pass: R = PᵀG → Adam(M, V) →
    G̃ = α P N̂, with M/V updated in place (input_output_aliases) and the
    intermediates R/N̂ never leaving VMEM. Returns (G̃, M', V').

    `zero`: the state is GaLore-ZeRO owner-sharded (see `_fused_kernel`).
    Falls back to the unfused kernels (via the pure-jnp composition) when the
    fused kernel's VMEM budget rejects the shape — see galore_fused.py."""
    return _fused_update(
        "left", galore_fused_k.galore_fused_adam_step,
        ref.galore_fused_adam_step, P, G, None, (M, V), count, quant=False,
        zero=zero, use_pallas=use_pallas, interpret=interpret,
        b1=b1, b2=b2, eps=eps, alpha=alpha)


def galore_fused_adam_step_right(P, G, M, V, count, *, b1=0.9, b2=0.999,
                                 eps=1e-8, alpha=1.0, zero=False,
                                 use_pallas=None, interpret=False):
    """Right-side fused leaf update: R = G P → Adam(M, V) → G̃ = α N̂ Pᵀ,
    for leaves whose SHORT side is the last dim (m > n; P is (..., n, r),
    M/V are (..., m, r)). A dedicated transposed-blockspec kernel — callers
    no longer swapaxes g/m/v to reuse the left kernel. Returns (G̃, M', V')."""
    return _fused_update(
        "right", galore_fused_k.galore_fused_adam_step_right,
        ref.galore_fused_adam_step_right, P, G, None, (M, V), count,
        quant=False, zero=zero, use_pallas=use_pallas, interpret=interpret,
        b1=b1, b2=b2, eps=eps, alpha=alpha)


def galore_fused_adam8_step(P, G, Mq, Ms, Vq, Vs, count, *, b1=0.9, b2=0.999,
                            eps=1e-8, alpha=1.0, stochastic=False, zero=False,
                            use_pallas=None, interpret=False):
    """INT8-moment fused leaf update (left side): R = PᵀG → dequant M/V in
    VMEM → Adam → requant → G̃ = α P N̂. Codes and scales are updated in
    place; fp32 moments never touch HBM. P may be a packed-INT4 qstate dict
    (in-kernel nibble dequant — no f32 projector in HBM either).
    Returns (G̃, Mq', Ms', Vq', Vs').

    Falls back to the reference composition where `fused_path` says so."""
    return _fused_update(
        "left", galore_fused_k.galore_fused_adam8_step,
        ref.galore_fused_adam8_step, P, G, None, (Mq, Ms, Vq, Vs), count,
        quant=True, zero=zero, use_pallas=use_pallas, interpret=interpret,
        b1=b1, b2=b2, eps=eps, alpha=alpha, stochastic=stochastic)


def galore_fused_adam8_step_right(P, G, Mq, Ms, Vq, Vs, count, *, b1=0.9,
                                  b2=0.999, eps=1e-8, alpha=1.0,
                                  stochastic=False, zero=False,
                                  use_pallas=None, interpret=False):
    """Right-side INT8-moment fused leaf update (blocks along the swept m)."""
    return _fused_update(
        "right", galore_fused_k.galore_fused_adam8_step_right,
        ref.galore_fused_adam8_step_right, P, G, None, (Mq, Ms, Vq, Vs),
        count, quant=True, zero=zero, use_pallas=use_pallas,
        interpret=interpret, b1=b1, b2=b2, eps=eps, alpha=alpha,
        stochastic=stochastic)


def galore_fused_adam_apply_step(P, G, W, M, V, count, *, b1=0.9, b2=0.999,
                                 eps=1e-8, alpha=1.0, eta=-1e-3, wd=0.0,
                                 use_pallas=None, interpret=False):
    """Weight-apply fused leaf update: W' = W + eta·(α P N̂ + wd·W) with W
    aliased in place — the remaining full-size f32 update write is gone.
    Returns (W', M', V'); the emit + chain path is the numerics oracle.
    P may be a packed-INT4 qstate dict."""
    return _fused_update(
        "left", galore_fused_k.galore_fused_adam_apply_step,
        ref.galore_fused_adam_apply_step, P, G, W, (M, V), count,
        quant=False, zero=False, use_pallas=use_pallas, interpret=interpret,
        b1=b1, b2=b2, eps=eps, alpha=alpha, eta=eta, wd=wd)


def galore_fused_adam_apply_step_right(P, G, W, M, V, count, *, b1=0.9,
                                       b2=0.999, eps=1e-8, alpha=1.0,
                                       eta=-1e-3, wd=0.0, use_pallas=None,
                                       interpret=False):
    return _fused_update(
        "right", galore_fused_k.galore_fused_adam_apply_step_right,
        ref.galore_fused_adam_apply_step_right, P, G, W, (M, V), count,
        quant=False, zero=False, use_pallas=use_pallas, interpret=interpret,
        b1=b1, b2=b2, eps=eps, alpha=alpha, eta=eta, wd=wd)


def galore_fused_adam8_apply_step(P, G, W, Mq, Ms, Vq, Vs, count, *, b1=0.9,
                                  b2=0.999, eps=1e-8, alpha=1.0, eta=-1e-3,
                                  wd=0.0, stochastic=False, use_pallas=None,
                                  interpret=False):
    """INT8 moments + in-place weight apply — the full 8-bit GaLore hot path
    in one launch (HBM sees G, W, uint8 codes, and with a qstate P the
    packed INT4 projector — nothing else)."""
    return _fused_update(
        "left", galore_fused_k.galore_fused_adam8_apply_step,
        ref.galore_fused_adam8_apply_step, P, G, W, (Mq, Ms, Vq, Vs), count,
        quant=True, zero=False, use_pallas=use_pallas, interpret=interpret,
        b1=b1, b2=b2, eps=eps, alpha=alpha, eta=eta, wd=wd,
        stochastic=stochastic)


def galore_fused_adam8_apply_step_right(P, G, W, Mq, Ms, Vq, Vs, count, *,
                                        b1=0.9, b2=0.999, eps=1e-8, alpha=1.0,
                                        eta=-1e-3, wd=0.0, stochastic=False,
                                        use_pallas=None, interpret=False):
    return _fused_update(
        "right", galore_fused_k.galore_fused_adam8_apply_step_right,
        ref.galore_fused_adam8_apply_step_right, P, G, W, (Mq, Ms, Vq, Vs),
        count, quant=True, zero=False, use_pallas=use_pallas,
        interpret=interpret, b1=b1, b2=b2, eps=eps, alpha=alpha, eta=eta,
        wd=wd, stochastic=stochastic)


def adam8_full_step(G, Mq, Ms, Vq, Vs, count, *, b1=0.9, b2=0.999, eps=1e-8,
                    stochastic=False, use_pallas=None, interpret=False):
    """Int8 Adam step of a full-shape leaf (one GaLore does not project):
    dequant M/V → Adam → requant, codes of G's shape and scales blocked by
    QBLOCK along the last axis (codec.quantize_axis(axis=-1)). Returns
    (update in G's dtype, Mq', Ms', Vq', Vs').

    Runs the epilogue's fold (`galore_fused.adam8_axis_update`, one
    kernel, moments updated in place) where the Pallas backend is selected,
    G is at least 2-D with a last dim of whole 128-lane rows (the
    in-kernel codebook gathers stay inside one row on the chip), and no
    multi-device mesh is active; else kernels/ref.py's XLA codec, the
    implementation of record. Recorded under `record_routes`."""
    rules = active_rules()
    fused = bool(_resolve(use_pallas) and G.ndim >= 2
                 and G.shape[-1] % _LANES == 0
                 and (rules is None or rules.mesh.size == 1))
    if _routes is not None:
        _routes.append(("full", tuple(G.shape),
                        "fused" if fused else "reference"))
    kw = dict(b1=b1, b2=b2, eps=eps, stochastic=stochastic)
    if fused:
        return galore_fused_k.adam8_axis_update(G, Mq, Ms, Vq, Vs, count,
                                                interpret=interpret, **kw)
    return ref.adam8_full_step(G, Mq, Ms, Vq, Vs, count, **kw)


def adam8bit_step(g_blocks, m_codes, m_scale, v_codes, v_scale, count,
                  *, b1=0.9, b2=0.999, eps=1e-8, use_pallas=None, interpret=False):
    """Fused dequant→Adam→requant on (nb, 256) blocks."""
    book_s = jnp.asarray(dynamic_codebook(True))
    book_u = jnp.asarray(dynamic_codebook(False))
    if _resolve(use_pallas):
        return _pallas(adam8bit_k.adam8bit_update,
                       g_blocks, m_codes, m_scale, v_codes, v_scale, count,
                       book_s, book_u, b1=b1, b2=b2, eps=eps,
                       interpret=interpret)
    return ref.adam8bit_update(
        g_blocks, m_codes, m_scale, v_codes, v_scale, count, book_s, book_u,
        b1=b1, b2=b2, eps=eps,
    )


def rmsnorm(x, scale, *, eps=1e-6, use_pallas=None, interpret=False):
    if _resolve(use_pallas):
        return _pallas(rmsnorm_k.rmsnorm, x, scale, eps=eps, interpret=interpret)
    return ref.rmsnorm(x, scale, eps)


def lowrank_adam_update(R, M, V, count, *, b1=0.9, b2=0.999, eps=1e-8):
    """Compact-space Adam on a pre-projected R (pure-jnp; XLA fuses the
    elementwise chain). On TPU the hot path should not call this at all —
    `galore_fused_adam_step` folds the projection, this update, and the
    back-projection into one kernel so R/N̂ never round-trip HBM (measured
    and analytic traffic in EXPERIMENTS.md §Perf)."""
    return ref.lowrank_adam_update(R, M, V, count, b1, b2, eps)
