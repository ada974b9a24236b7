"""The main-path Pallas kernels compile for a TPU v5e at LLaMA-1B widths.

Every other kernel test runs the kernel bodies in interpret mode, which
cannot see what the TPU compiler (Mosaic) refuses: unaligned block shapes,
more VMEM than a kernel may use, operations it cannot lower. These cases
compile the kernels the LLaMA-1B GaLore step dispatches to (rank 512, leaf
shapes 2048×2048, 2048×5461 and 5461×2048, two stacked layers) for a
described v5e chip — no chip is needed — and check that the compiled
program holds the kernel, as do the full-shape int8 fold at Qwen2-7B's
embedding and stacked K/V widths; two more compile the GaLore-ZeRO form for a
described four-chip v5e:2x2, where each owner runs the kernel on its own
rank block. They do not run anything.
"""
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (
    AxisType,
    Mesh,
    NamedSharding,
    PartitionSpec,
    SingleDeviceSharding,
)

from repro.kernels import galore_fused as F
from repro.kernels import galore_project as GP
from repro.kernels import ops
from repro.launch.mesh import default_rules
from repro.utils import sharding_context

R = 512   # the paper's GaLore rank for LLaMA-1B
L = 2     # stacked layers: the leading batch-grid axis


@pytest.fixture(scope="module")
def v5e_devices():
    """The four devices of a described v5e:2x2 host, with JAX's persistent
    compilation cache off: a compile for a described chip is written to
    the cache but cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed / no libtpu
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _int4_p(sh, short):
    nbp = -(-short // 128)
    return {"q": _sds(sh, (L, nbp * 64, R), jnp.uint8),
            "scale": _sds(sh, (L, nbp, R), jnp.float32)}


def _int8_moments(sh, shape, blocked_axis):
    scales = list(shape)
    scales[blocked_axis] = -(-shape[blocked_axis] // 128)
    codes = _sds(sh, shape, jnp.uint8)
    sc = _sds(sh, tuple(scales), jnp.float32)
    return codes, sc, codes, sc


def _case(name, sh):
    """(function, abstract args) of one kernel variant at LLaMA-1B widths;
    G and W are bf16 like the model's weights and gradients."""
    cnt = _sds(sh, (), jnp.int32)
    bf16, f32 = jnp.bfloat16, jnp.float32
    if name == "fp32_left_2048x2048":
        m = n = 2048
        return (F.galore_fused_adam_step,
                (_sds(sh, (L, m, R), f32), _sds(sh, (L, m, n), bf16),
                 _sds(sh, (L, R, n), f32), _sds(sh, (L, R, n), f32), cnt))
    if name == "fp32_right_5461x2048":
        m, n = 5461, 2048
        return (F.galore_fused_adam_step_right,
                (_sds(sh, (L, n, R), f32), _sds(sh, (L, m, n), bf16),
                 _sds(sh, (L, m, R), f32), _sds(sh, (L, m, R), f32), cnt))
    if name == "int8_left_2048x5461":
        m, n = 2048, 5461
        return (F.galore_fused_adam8_step,
                (_sds(sh, (L, m, R), f32), _sds(sh, (L, m, n), bf16),
                 *_int8_moments(sh, (L, R, n), 2), cnt))
    if name == "int8_int4p_left_2048x2048":
        m = n = 2048
        return (F.galore_fused_adam8_step,
                (_int4_p(sh, m), _sds(sh, (L, m, n), bf16),
                 *_int8_moments(sh, (L, R, n), 2), cnt))
    if name == "int8_int4p_right_5461x2048":
        m, n = 5461, 2048
        return (F.galore_fused_adam8_step_right,
                (_int4_p(sh, n), _sds(sh, (L, m, n), bf16),
                 *_int8_moments(sh, (L, m, R), 1), cnt))
    if name == "apply_int8_int4p_left_2048x5461":
        m, n = 2048, 5461
        return (F.galore_fused_adam8_apply_step,
                (_int4_p(sh, m), _sds(sh, (L, m, n), bf16),
                 _sds(sh, (L, m, n), bf16),
                 *_int8_moments(sh, (L, R, n), 2), cnt))
    if name == "int8_right_rank32_lane_padded":
        # a rank that is not a multiple of the 128 lanes (a rank-512 leaf's
        # block on each of 16 GaLore-ZeRO owners): kernels/ops.py pads it
        m, n, r = 5461, 2048, 32
        codes = _sds(sh, (L, m, r), jnp.uint8)
        sc = _sds(sh, (L, -(-m // 128), r), jnp.float32)
        return (lambda *a: ops.galore_fused_adam8_step_right(
                    *a, use_pallas=True),
                (_sds(sh, (L, n, r), f32), _sds(sh, (L, m, n), bf16),
                 codes, sc, codes, sc, cnt))
    if name.startswith("fold_"):
        # full-shape int8 Adam at Qwen2-7B widths: the 38,144 x 3,584 tied
        # embedding, the 7 stacked 3,584 x 512 K (or V) projections and a
        # stack of 7 norm scales (or q biases)
        shape = {"fold_embedding_38144x3584": (38144, 3584),
                 "fold_kv_7x3584x512": (7, 3584, 512),
                 "fold_norm_7x3584": (7, 3584)}[name]
        return (F.adam8_axis_update,
                (_sds(sh, shape, bf16), *_int8_moments(sh, shape, -1), cnt))
    if name == "project_5461x2048":
        return (GP.galore_project,
                (_sds(sh, (L, 5461, R), f32), _sds(sh, (L, 5461, 2048), bf16)))
    if name == "project_back_5461x2048":
        return (lambda P, N: GP.galore_project_back(P, N, 0.25),
                (_sds(sh, (L, 5461, R), f32), _sds(sh, (L, R, 2048), f32)))
    raise KeyError(name)


@pytest.mark.parametrize("name", [
    "fp32_left_2048x2048",
    "fp32_right_5461x2048",
    "int8_left_2048x5461",
    "int8_int4p_left_2048x2048",
    "int8_int4p_right_5461x2048",
    "apply_int8_int4p_left_2048x5461",
    "int8_right_rank32_lane_padded",
    "fold_embedding_38144x3584",
    "fold_kv_7x3584x512",
    "fold_norm_7x3584",
    "project_5461x2048",
    "project_back_5461x2048",
])
def test_kernel_compiles_for_v5e(v5e_devices, name):
    fn, args = _case(name, SingleDeviceSharding(v5e_devices[0]))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fold_call_is_not_read_as_a_galore_kernel_call(v5e_devices):
    """`galore_kernel_roofline` counts the calls that bench/trace.py's
    `galore_call` reads as fused GaLore calls off their compiled operand
    shapes. The full-shape int8 fold's call (codes of G's shape) must not
    read as one, at the stacked K/V widths of Qwen2-7B; a GaLore int8 call
    compiled beside it still does."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench.trace import galore_call, parse_hlo

    sh = SingleDeviceSharding(v5e_devices[0])
    bf16, f32 = jnp.bfloat16, jnp.float32
    kv, (m, n) = (7, 3584, 512), (256, 1024)

    def both(Gk, Mk, Sk, P, G, Mg, Sg, cnt):
        fold = F.adam8_axis_update(Gk, Mk, Sk, Mk, Sk, cnt)
        galore = F.galore_fused_adam8_step(P, G, Mg, Sg, Mg, Sg, cnt)
        return fold, galore

    text = jax.jit(both).lower(
        _sds(sh, kv, bf16), _sds(sh, kv, jnp.uint8),
        _sds(sh, kv[:2] + (kv[2] // 128,), f32),
        _sds(sh, (L, m, R), f32), _sds(sh, (L, m, n), bf16),
        _sds(sh, (L, R, n), jnp.uint8), _sds(sh, (L, R, n // 128), f32),
        _sds(sh, (), jnp.int32)).compile().as_text()
    calls = [info for info in parse_hlo(text).values()
             if info["custom"] and info["target"] == "tpu_custom_call"]
    read = sorted((galore_call(info) for info in calls), key=str)
    assert len(calls) == 2 and read == [("left", L, m, n, R), None], read


@pytest.mark.parametrize("side", ["left", "right"])
def test_zero_fused_kernel_compiles_for_v5e_2x2(v5e_devices, side):
    """Under GaLore-ZeRO on a 4-chip data mesh the int8+int4P kernel runs
    on each owner's rank block: the compiled program holds the kernel,
    gathers no uint8 state (codes stay on their owners) and returns the
    new moments sharded on the rank dim."""
    mesh = Mesh(np.array(v5e_devices).reshape(4, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    rules = default_rules(mesh)
    rep = NamedSharding(mesh, PartitionSpec())
    m, n = (2048, 2048) if side == "left" else (5461, 2048)
    short = m if side == "left" else n
    rank_last = side == "right"
    own = lambda nd: NamedSharding(mesh, PartitionSpec(
        *([None] * (nd - 1) + ["data"] if rank_last
          else [None] * (nd - 2) + ["data", None])))
    mom = (L, R, n) if side == "left" else (L, m, R)
    sc = (L, R, -(-n // 128)) if side == "left" else (L, -(-m // 128), R)
    nbp = -(-short // 128)
    P = {"q": _sds(rep, (L, nbp * 64, R), jnp.uint8),
         "scale": _sds(rep, (L, nbp, R), jnp.float32)}
    codes, scales = _sds(own(3), mom, jnp.uint8), _sds(own(3), sc, jnp.float32)
    fn = (ops.galore_fused_adam8_step if side == "left"
          else ops.galore_fused_adam8_step_right)
    with sharding_context(rules):
        compiled = jax.jit(lambda *a: fn(*a, zero=True, use_pallas=True)).lower(
            P, _sds(rep, (L, m, n), jnp.bfloat16), codes, scales, codes,
            scales, _sds(rep, (), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not re.search(r"= u8\[[^\]]*\][^ ]* all-gather", text)
    rank_dim = 1 if side == "left" else 2
    for out in compiled.output_shardings[1:]:
        spec = tuple(out.spec) + (None,) * (3 - len(out.spec))
        assert spec[rank_dim] == "data", out
