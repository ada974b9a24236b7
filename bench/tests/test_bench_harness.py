"""CPU tests of the benchmark's harness: trace reduction, counts, refusals,
metric discovery, and a whole run of a tiny cell against the reference.

They run on the CPU (JAX_PLATFORMS=cpu) at small sizes; no test here
describes or touches a TPU.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import arch, counts, trace  # noqa: E402
from bench.metrics import Context, load_reader  # noqa: E402


# --- trace reduction --------------------------------------------------------

HLO = """\
HloModule jit_pinned_step, entry_computation_layout={()}

fused_computation.3 {
  p.0 = bf16[16,256,64]{2,1,0} parameter(0)
  ROOT m.1 = bf16[16,256,64]{2,1,0} multiply(p.0, p.0), metadata={op_name="jit(pinned_step)/mul" source_file="/x/src/repro/models/layers.py" source_line=120}
}

ENTRY main {
  fusion.1 = bf16[16,256,64]{2,1,0} fusion(a), kind=kLoop, calls=fused_computation.3
  fusion.2 = f32[6,64]{1,0} fusion(b), kind=kLoop, calls=fused_computation.9, metadata={op_name="jit(pinned_step)/adam" source_file="/x/src/repro/core/galore.py" source_line=9}
  custom-call.4 = (f32[6,128,512]{2,1,0}, u8[6,32,512]{2,1,0}, f32[6,32,4]{2,1,0}, u8[6,32,512]{2,1,0}, f32[6,32,4]{2,1,0}) custom-call(u8[6,64,32]{2,1,0} q, f32[6,1,32]{2,1,0} s, bf16[6,128,512]{2,1,0} g, u8[6,32,512]{2,1,0} mq, f32[6,32,4]{2,1,0} ms, u8[6,32,512]{2,1,0} vq, f32[6,32,4]{2,1,0} vs, s32[] c), custom_call_target="tpu_custom_call", metadata={op_name="jit(pinned_step)/pallas_call" source_file="/x/src/repro/kernels/ops.py" source_line=77}
  copy.7 = f32[6,64]{1,0} copy(fusion.2)
  fusion.8 = f32[6,64]{1,0} fusion(copy.7), kind=kLoop, calls=fused_computation.10, metadata={op_name="jit(pinned_step)/searchsorted" stack_frame_id=3}
  g.1 = bf16[6,128,512]{2,1,0:T(8,128)(2,1)} fusion(a), kind=kLoop, calls=fused_computation.3
  q.2 = u8[6,64,32]{2,1,0:T(8,128)(4,1)S(1)} copy(b)
  pinned_step.9 = (f32[6,128,512]{2,1,0:T(8,128)}, u8[6,32,512]{2,1,0:T(8,128)(4,1)}, f32[6,32,4]{2,1,0:T(8,128)}, u8[6,32,512]{2,1,0:T(8,128)(4,1)}, f32[6,32,4]{2,1,0:T(8,128)}) custom-call(q.2, s, g.1, mq, ms, vq, vs, c), custom_call_target="tpu_custom_call", metadata={op_name="jit(pinned_step)/pallas_call" stack_frame_id=3}
}
"""

# the stack-frame tables the TPU compiler prints in place of source_file
HLO_TABLES = """HloModule jit_pinned_step, is_scheduled=true

FileNames
1 "/x/bench/run.py"
2 "/x/src/repro/distributed/step.py"
3 "/x/src/repro/quant/codec.py"

FunctionNames
1 "drive"

FileLocations
1 {file_name_id=1 function_name_id=1 line=1 end_line=1 column=1 end_column=2}
2 {file_name_id=2 function_name_id=1 line=9 end_line=9 column=1 end_column=2}
3 {file_name_id=3 function_name_id=1 line=7 end_line=7 column=1 end_column=2}

StackFrames
1 {file_location_id=1 parent_frame_id=0}
2 {file_location_id=2 parent_frame_id=1}
3 {file_location_id=3 parent_frame_id=2}

""" + HLO.split("\n", 1)[1]


def test_parse_hlo_sources_and_custom_call():
    h = trace.parse_hlo(HLO)
    assert trace.layer_of(h["fusion.1"]["source"]) == "model"  # from its computation
    assert trace.layer_of(h["fusion.2"]["source"]) == "optimizer"
    assert trace.layer_of(h["copy.7"]["source"]) == "unattributed"
    h = trace.parse_hlo(HLO_TABLES)
    assert h["fusion.8"]["source"] == "/x/src/repro/quant/codec.py"
    assert trace.layer_of(h["fusion.8"]["source"]) == "optimizer"
    cc = h["custom-call.4"]
    assert cc["custom"] and cc["target"] == "tpu_custom_call"
    assert trace.galore_call(cc) == ("left", 6, 128, 512, 32)
    # the TPU compiler's form: operands by name alone, layouts with brackets
    tpu = trace.parse_hlo(HLO)["pinned_step.9"]
    assert tpu["custom"] and ("bf16", (6, 128, 512)) in tpu["operands"]
    assert ("u8", (6, 64, 32)) in tpu["operands"]
    assert tpu["results"][0] == ("f32", (6, 128, 512))
    assert trace.galore_call(tpu) is None  # its moments are not defined here
    assert trace.nbytes([("bf16", (6, 128, 512))]) == 6 * 128 * 512 * 2


def test_busy_union_gaps_and_attribution():
    # two overlapping ops, a gap of 30 ns, an op of another program
    step = "jit_pinned_step(123)"
    ops = [("fusion.1", step, 0, 50, False), ("fusion.2", step, 40, 100, False),
           ("custom-call.4", step, 130, 200, False),
           ("fusion.1", "jit__make(9)", 200, 210, False), ("copy.7", step, 210, 220, False),
           ("while.3", step, 0, 220, True)]
    hosts = [("train_loop", 0, 1000), ("bench.on_step", 100, 135)]
    out = trace.reduce({"/device:TPU:0": ops}, hosts, trace.parse_hlo(HLO),
                       window_s=400e-9)
    assert out["busy_s"] == pytest.approx(190e-9)
    layers = out["layers"]
    assert layers["model"] == pytest.approx(50e-9)
    assert layers["optimizer"] == pytest.approx(60e-9)
    assert layers["kernels"] == pytest.approx(70e-9)
    # an op of another program and one without a source: unattributed
    assert layers["unattributed"] == pytest.approx(20e-9)
    assert out["breakdown"]["idle_gaps"] == [["bench.on_step", pytest.approx(30e-9)]]
    (k,) = out["kernels"]
    assert k["call"] == ("left", 6, 128, 512, 32) and k["count"] == 1
    ctx = Context(cell=None, peaks={"bf16_flops_per_s": 197e12,
                                    "hbm_bytes_per_s": 819e9},
                  run={"trace": out, "steps": 1}, n_chips=1)
    assert load_reader("device_idle_share").read(ctx) == pytest.approx(52.5)
    assert load_reader("model_ms_per_step").read(ctx) == pytest.approx(50e-6)
    assert load_reader("galore_kernel_ms_per_step").read(ctx) == pytest.approx(70e-6)


# an op in a named scope inside a fusion's computation, ops of one program
# file in a scope and outside any
HLO_SCOPES = """HloModule jit_pinned_step

fused_computation.5 {
  p.0 = f32[64]{0} parameter(0)
  ROOT s.1 = s32[64]{0} compare(p.0, p.0), direction=LT, metadata={op_name="jit(pinned_step)/transpose(jvp())/while/body/closed_call/checkpoint/int8_quantize/searchsorted" source_file="/x/src/repro/quant/codec.py" source_line=3}
}

ENTRY main {
  fusion.5 = s32[64]{0} fusion(a), kind=kLoop, calls=fused_computation.5
  fusion.6 = f32[64]{0} fusion(b), kind=kLoop, calls=fused_computation.9, metadata={op_name="jit(pinned_step)/jvp()/moe/dot_general" source_file="/x/src/repro/models/moe.py" source_line=9}
  fusion.7 = f32[64]{0} fusion(c), kind=kLoop, calls=fused_computation.9, metadata={op_name="jit(pinned_step)/jvp()/dot_general" source_file="/x/src/repro/models/moe.py" source_line=12}
  copy.8 = f32[64]{0} copy(fusion.6)
}
"""


def test_reduce_times_program_files_and_named_scopes():
    step = "jit_pinned_step(1)"
    ops = [("fusion.5", step, 0, 10, False), ("fusion.6", step, 10, 30, False),
           ("fusion.7", step, 30, 60, False), ("copy.8", step, 60, 70, False),
           ("fusion.5", "jit_other(2)", 70, 80, False)]
    out = trace.reduce({"/device:TPU:0": ops}, [], trace.parse_hlo(HLO_SCOPES),
                       window_s=100e-9)
    assert out["files"] == {"quant/codec.py": pytest.approx(10e-9),
                            "models/moe.py": pytest.approx(50e-9)}
    assert out["scopes"] == {"int8_quantize": pytest.approx(10e-9),
                             "moe": pytest.approx(20e-9)}
    # the split by layer is as before: another program's op is unattributed
    assert out["layers"] == {"optimizer": pytest.approx(10e-9),
                             "model": pytest.approx(50e-9),
                             "unattributed": pytest.approx(20e-9)}


@pytest.mark.parametrize("op_name,scope", [
    ("jit(f)/jvp()/while/body/closed_call/mix/int8_quantize/mul", "int8_quantize"),
    ("jit(f)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/mix/dot_general", "mix"),
    ("jit(f)/cond/branch_1_fun/moe/add", "moe"),
    ("jit(f)/transpose(jvp())/while/body/add", None),
    ("jit(pinned_step)/pallas_call", None),
])
def test_innermost_scope_passes_over_what_jax_writes(op_name, scope):
    assert trace.innermost_scope(op_name) == scope


@pytest.mark.parametrize("operands,call", [
    # (L, m, n) with int4 projector codes: as the kernels of a dense stack
    ([("u8", (6, 64, 32)), ("f32", (6, 1, 32)), ("bf16", (6, 128, 512)),
      ("u8", (6, 32, 512)), ("f32", (6, 32, 4)), ("s32", ())],
     ("left", 6, 128, 512, 32)),
    ([("u8", (7, 256, 896)), ("bf16", (7, 18944, 3584)), ("u8", (7, 18944, 896)),
      ("f32", (7, 18944, 7))], ("right", 7, 18944, 3584, 896)),
    # (L, E, m, n): stacked experts, each (layer, expert) a matrix of its own
    ([("u8", (2, 4, 64, 32)), ("bf16", (2, 4, 128, 512)), ("u8", (2, 4, 32, 512)),
      ("f32", (2, 4, 32, 4))], ("left", 8, 128, 512, 32)),
    ([("bf16", (2, 4, 512, 128)), ("u8", (2, 4, 512, 32)), ("f32", (2, 4, 512, 1))],
     ("right", 8, 512, 128, 32)),
    # the full-shape int8 fold: its codes have G's shape
    ([("bf16", (7, 3584, 512)), ("u8", (7, 3584, 512)), ("f32", (7, 3584, 4)),
      ("u8", (7, 3584, 512)), ("f32", (7, 3584, 4)), ("s32", ())], None),
])
def test_galore_call_reads_any_leading_axes(operands, call):
    assert trace.galore_call({"custom": True, "operands": operands}) == call


def test_readers_return_nothing_without_a_trace():
    ctx = Context(cell=None, peaks={}, run={"steps": 3}, n_chips=1)
    for name in ("device_idle_share", "model_ms_per_step", "optimizer_ms_per_step",
                 "galore_kernel_ms_per_step", "galore_kernel_roofline", "step_mfu"):
        assert load_reader(name).read(ctx) is None


def test_dummy_metric_file_is_found_by_name(tmp_path, monkeypatch):
    import bench.metrics as bm

    src = os.path.join(bm.HERE)
    dst = tmp_path / "metrics"
    shutil.copytree(src, dst)
    (dst / "dummy_share.py").write_text("def read(ctx):\n    return 42.0\n")
    monkeypatch.setattr(bm, "HERE", str(dst))
    from bench import run

    bench = {"per_layer": [
        {"name": "dummy_share", "unit": "%", "workloads": ["a.b"]},
        {"name": "model_ms_per_step", "unit": "ms"}]}
    cell = type("C", (), {"name": "a.b"})
    ctx = Context(cell=cell, peaks={}, run={"steps": 1}, n_chips=1)
    assert run.per_layer_metrics(bench, cell, ctx) == {
        "dummy_share": {"value": 42.0, "unit": "%"}}


# --- counts -----------------------------------------------------------------


def test_galore_kernel_counts_by_hand():
    # left, one layer, m 128, n 512, r 32, bf16 G
    flops, nbytes = counts.galore_kernel("left", 1, 128, 512, 32)
    assert flops == 2 * 128 * 512 * 32 * 2
    g, upd = 2 * 128 * 512, 4 * 128 * 512
    proj = 0.5 * 128 * 32 + 4 * 1 * 32          # int4 codes + one scale block
    moments = 2 * 2 * (32 * 512 + 4 * 32 * 4)   # m, v codes + scales, read + write
    assert nbytes == pytest.approx(g + upd + proj + moments)
    # the right side swaps the roles of m and n
    assert counts.galore_kernel("right", 3, 512, 128, 32) == (3 * flops, 3 * nbytes)


def test_kernel_bytes_match_the_real_operands():
    """The analytic bytes equal the custom call's operands and results."""
    cc = trace.parse_hlo(HLO)["custom-call.4"]
    _, nbytes = counts.galore_kernel(*trace.galore_call(cc))
    real = trace.nbytes(cc["operands"]) + trace.nbytes(cc["results"])
    # the scalar step count (4 bytes) is the only operand the model leaves out
    assert real - nbytes == 4


def test_model_flops_by_hand():
    c = {"model_type": "qwen2", "hidden_size": 8, "num_attention_heads": 2,
         "num_key_value_heads": 1, "intermediate_size": 16,
         "num_hidden_layers": 3, "vocab_size": 10}
    dense = arch.of(c)
    per_layer = 8 * 8 * 2 + 8 * 4 * 2 + 3 * 8 * 16
    n = 3 * per_layer + 10 * 8
    assert dense.matmul_params(c) == n
    assert dense.flops_per_token(c, 5) == 6 * n + 12 * 3 * 2 * 4 * 5


def test_internlm2_stage_flops():
    """39.8 TFLOP per 4096-token update for the InternLM2-20B stage (4 layers
    of 390.1 M matmul weights, a 7712-row head, 256-token attention)."""
    c = {"model_type": "internlm2", "hidden_size": 6144, "intermediate_size": 16384,
         "num_attention_heads": 48, "num_key_value_heads": 8, "num_hidden_layers": 4,
         "vocab_size": 7712}
    assert arch.of(c).flops_per_token(c, 256) * 4096 == pytest.approx(39.82e12, rel=1e-3)


def test_bigram_map_is_one_cycle_through_the_vocabulary():
    import jax
    import numpy as np

    from bench.data import bigram_successor

    succ = np.asarray(bigram_successor(jax.random.PRNGKey(7), 1000))
    x, seen = 0, set()
    for _ in range(1000):
        seen.add(x)
        x = int(succ[x])
    assert x == 0 and len(seen) == 1000


def test_token_rows_stay_c4_like():
    """Over seeds, the rows of the cell's traffic keep a Zipf head: no token
    fills more than 5 % of a batch, and every batch holds over 1500 ids."""
    import numpy as np

    from bench.cell import load_cell
    from bench.data import TokenStream

    cell = load_cell("qwen2-7b-pp4.b16")
    for seed in (2718282001, 2**33 + 5):
        stream = TokenStream(cell.traffic, cell.config["vocab_size"], cell.rows, seed)
        for step in (0, 1):
            tokens = np.asarray(stream.batch(step)["tokens"])
            _, counts = np.unique(tokens, return_counts=True)
            assert counts.max() < 0.05 * tokens.size, (seed, step, counts.max())
            assert len(counts) > 1500, (seed, step, len(counts))


def test_change_is_compared_by_the_worst_leaf_of_each_group():
    """A leaf whose first update is under half a spacing of its weights' type
    is left out; each group is read by its worst leaf."""
    import numpy as np

    from bench import check

    keys = [("w", 0), ("w", 1), ("emb", 0), ("scale", 0), ("kbias", 0)]
    ref = {"losses": [1.0], "galore": [["w", 0], ["w", 1]],
           "grad_norms": dict(zip(keys, [1.0, 1.0, 1.0, 1.0, 1e-6])),
           "first_step_rms": dict(zip(keys, [1e-4, 1e-4, 1e-3, 1e-3, 1e-3])),
           "w0_half_ulp_rms": dict(zip(keys, [6e-5, 6e-5, 6e-5, 3.9e-3, 0.0])),
           "change_norms": dict(zip(keys, [1.0, 2.0, 4.0, 0.1, 0.1])),
           "first_grads": {("emb", 0): np.ones(4) / 2}}
    prog = {"losses": [1.0], "grad_norms": ref["grad_norms"],
            "first_grads": ref["first_grads"],
            "change_norms": dict(zip(keys, [1.1, 2.0, 3.0, 0.0, 0.0]))}
    assert check.groups(ref) == {"galore": [("w", 0), ("w", 1)],
                                 "adam": [("emb", 0)], "unresolved": [("scale", 0)]}
    nums = check.numbers(prog, ref)
    assert nums["change_galore"] == (pytest.approx(0.05), "w[0]")  # 0.1 / median 2
    assert nums["change_adam"] == (pytest.approx(0.25), "emb[0]")
    assert nums["change_unresolved"] == (pytest.approx(0.05), "scale[0]")
    ok, out = check.verdict(nums, {"change_galore": 0.1, "change_adam": 0.2})
    assert not ok and set(out) == {"change_galore", "change_adam"}


def test_grad_is_also_compared_element_by_element():
    """A gradient with the right norm and the wrong elements passes `grad`
    and fails `grad_elem`; a leaf under the median is read against the
    median leaf's norm."""
    import numpy as np

    from bench import check

    keys = [("w", 0), ("emb", 0), ("bias", 0)]
    g = np.array([3.0, -4.0, 0.0, 0.0])
    ref = {"losses": [1.0], "grad_norms": dict(zip(keys, [5.0, 5.0, 0.5])),
           "first_grads": {("emb", 0): g, ("bias", 0): np.array([0.3, 0.4])}}
    prog = {"losses": [1.0], "grad_norms": ref["grad_norms"],
            "first_grads": {("emb", 0): g[[1, 0, 2, 3]], ("bias", 0): np.array([0.4, 0.3])}}
    gaps = check.grad_elem_gaps(prog, ref)
    assert gaps[("emb", 0)] == pytest.approx(7 * 2 ** 0.5 / 5)
    assert gaps[("bias", 0)] == pytest.approx(0.1 * 2 ** 0.5 / 5)  # over the median, 5
    same = {"change_norms": dict.fromkeys(keys, 1.0)}
    ref.update(same, galore=[], first_step_rms=dict.fromkeys(keys, 1.0),
               w0_half_ulp_rms=dict.fromkeys(keys, 0.0))
    nums = {k: v for k, v in check.numbers({**prog, **same}, ref).items()
            if k.startswith("grad")}
    assert nums == {"grad": (0.0, "w[0]"),
                    "grad_elem": (pytest.approx(7 * 2 ** 0.5 / 5), "emb[0]")}


def test_op_events_name_their_instruction():
    ev = ("%while.1345 = (s32[]{:T(128)}, f32[255]{0:T(256)}) while((s32[]{:T(128)}, "
          "f32[255]{0:T(256)}) %tuple.9957), condition=%c, body=%b")
    assert trace.op_name(ev) == "while.1345"
    assert trace._CONTAINER.search(ev)
    fus = "%fusion.83 = f32[136708096]{0:T(1024)} fusion(f32[256]{0:T(256)} %c), kind=kCustom"
    assert trace.op_name(fus) == "fusion.83" and not trace._CONTAINER.search(fus)


# --- refusals ----------------------------------------------------------------


def _bench_cmd(*extra, cwd=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "bench", "run.py"), "--workload",
         "qwen2-7b-pp4.b16", "--seed", "3", "--seconds", "1",
         "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    p = _bench_cmd()
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_an_unknown_device_kind():
    from bench import run

    with pytest.raises(SystemExit) as e:
        run.load_peaks("TPU v99")
    assert e.value.code != 0
    assert run.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_refuses_without_the_program(tmp_path):
    """A checkout that holds only BENCHMARK.json and bench/ gives no result."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    p = _bench_cmd(cwd=str(tmp_path))
    assert p.returncode != 0 and p.stdout.strip() == ""
