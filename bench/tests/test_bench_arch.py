"""CPU tests of the seam between the harness and an architecture (bench/arch/).

The dense module reproduces numbers pinned from the harness as it was before
the dense decoder moved into bench/arch/dense.py: the weights of a tiny
dense configuration, the float32 reference's readings on them, and the FLOPs
per token of the Qwen2-7B stage. A toy architecture written as a module
outside bench/, with a tuple node in its tree and a leaf with a leading
expert axis, runs through the whole harness: the proof that another
architecture is added as new files.
"""
import json
import os
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SEED = 123456789012


def _tiny_dense() -> dict:
    with open(os.path.join(ROOT, "bench", "configs", "qwen2-7b-pp4.json")) as f:
        config = json.load(f)
    config.update(hidden_size=128, intermediate_size=256, num_attention_heads=4,
                  num_key_value_heads=1, num_hidden_layers=2, vocab_size=500)
    config["assumed"]["galore"]["rank"] = 32
    config["assumed"]["train"]["lr"] = 1e-2
    return config


# --- the dense module against the harness it was moved from ------------------

# (sum, sum of |x|) of each leaf of the tiny dense tree at SEED, as the
# harness made it before the move (weights bit for bit: crc32 of the path,
# then the instance's index, folded into the seed's key)
WEIGHT_SUMS = {
    "blocks/attn/bk": (0.0, 0.0),
    "blocks/attn/bq": (0.0, 0.0),
    "blocks/attn/bv": (0.0, 0.0),
    "blocks/attn/wk": (-5.726407706737518, 568.742169201374),
    "blocks/attn/wo": (-9.437587484717369, 2304.834518954158),
    "blocks/attn/wq": (17.377232432365417, 2302.866310596466),
    "blocks/attn/wv": (5.301466226577759, 578.3914935588837),
    "blocks/ffn/down": (-4.454620283097029, 3281.9339071400464),
    "blocks/ffn/gate": (46.098492950201035, 4626.197766810656),
    "blocks/ffn/up": (22.70304425060749, 4626.856370046735),
    "blocks/ln1/scale": (256.0, 256.0),
    "blocks/ln2/scale": (256.0, 256.0),
    "embed/embedding": (1.3999833334237337, 4639.822822904214),
    "final_norm/scale": (128.0, 128.0),
}

# the float32 reference's readings of two steps at SEED on the tiny dense
# configuration (C4-like rows of 32 tokens, 4 rows), before the move
LOSSES = (6.795817852020264, 6.721397876739502)
GRAD = {
    "blocks/attn/bk[0]": 0.00879072304815054,
    "blocks/attn/bk[1]": 0.0024077631533145905,
    "blocks/attn/bq[0]": 0.021091271191835403,
    "blocks/attn/bq[1]": 0.006376564968377352,
    "blocks/attn/bv[0]": 0.10299750417470932,
    "blocks/attn/bv[1]": 0.029553603380918503,
    "blocks/attn/wk[0]": 0.2536779046058655,
    "blocks/attn/wk[1]": 0.06778079271316528,
    "blocks/attn/wo[0]": 0.33146727085113525,
    "blocks/attn/wo[1]": 0.15990859270095825,
    "blocks/attn/wq[0]": 0.23748594522476196,
    "blocks/attn/wq[1]": 0.06511008739471436,
    "blocks/attn/wv[0]": 0.361216276884079,
    "blocks/attn/wv[1]": 0.1671159863471985,
    "blocks/ffn/down[0]": 0.28562039136886597,
    "blocks/ffn/down[1]": 0.15333150327205658,
    "blocks/ffn/gate[0]": 0.21299365162849426,
    "blocks/ffn/gate[1]": 0.11517216265201569,
    "blocks/ffn/up[0]": 0.2093527764081955,
    "blocks/ffn/up[1]": 0.11124783754348755,
    "blocks/ln1/scale[0]": 0.045703887939453125,
    "blocks/ln1/scale[1]": 0.0179581381380558,
    "blocks/ln2/scale[0]": 0.02924073487520218,
    "blocks/ln2/scale[1]": 0.015218664892017841,
    "embed/embedding[0]": 0.5504528880119324,
    "final_norm/scale[0]": 0.02882263995707035,
}
CHANGE = {
    "blocks/attn/bk[0]": 0.08738411217927933,
    "blocks/attn/bk[1]": 0.08134914189577103,
    "blocks/attn/bq[0]": 0.1719878911972046,
    "blocks/attn/bq[1]": 0.17249631881713867,
    "blocks/attn/bv[0]": 0.09186004847288132,
    "blocks/attn/bv[1]": 0.08197414875030518,
    "blocks/attn/wk[0]": 0.9863916039466858,
    "blocks/attn/wk[1]": 0.9765787124633789,
    "blocks/attn/wo[0]": 0.24716341495513916,
    "blocks/attn/wo[1]": 0.2415226250886917,
    "blocks/attn/wq[0]": 0.25172314047813416,
    "blocks/attn/wq[1]": 0.24929369986057281,
    "blocks/attn/wv[0]": 0.9939556121826172,
    "blocks/attn/wv[1]": 0.9699031710624695,
    "blocks/ffn/down[0]": 0.3557990789413452,
    "blocks/ffn/down[1]": 0.35420113801956177,
    "blocks/ffn/gate[0]": 0.3481307625770569,
    "blocks/ffn/gate[1]": 0.35129836201667786,
    "blocks/ffn/up[0]": 0.34942007064819336,
    "blocks/ffn/up[1]": 0.3483748435974121,
    "blocks/ln1/scale[0]": 0.17859140038490295,
    "blocks/ln1/scale[1]": 0.17647114396095276,
    "blocks/ln2/scale[0]": 0.1739766150712967,
    "blocks/ln2/scale[1]": 0.17477190494537354,
    "embed/embedding[0]": 3.8937180042266846,
    "final_norm/scale[0]": 0.19174087047576904,
}


def test_dense_weights_match_the_pinned_tree():
    import numpy as np

    from bench import weights as bw

    tree = bw.init_params(_tiny_dense(), SEED)
    flat = bw.flat_paths(tree)
    assert set(flat) == set(WEIGHT_SUMS)
    for path, (total, total_abs) in WEIGHT_SUMS.items():
        a = np.asarray(flat[path], np.float64)
        assert (a.sum(), np.abs(a).sum()) == (
            pytest.approx(total, rel=1e-9, abs=1e-6),
            pytest.approx(total_abs, rel=1e-9)), path


def test_dense_reference_matches_the_pinned_readings():
    import jax

    from bench.data import TokenStream
    from bench.reference import galore as rg

    config = _tiny_dense()
    with open(os.path.join(ROOT, "bench", "traffic", "c4-seq256-mb16.json")) as f:
        traffic = json.load(f)
    traffic.update(seq_len=32, micro_batch=4)
    stream = TokenStream(traffic, config["vocab_size"], 4, SEED)
    with jax.default_matmul_precision("highest"):
        r = rg.run(config, SEED, [stream.batch(0), stream.batch(1)])
    name = lambda k: f"{k[0]}[{k[1]}]"  # noqa: E731
    assert tuple(r["losses"]) == pytest.approx(LOSSES, rel=1e-6)
    assert {name(k): v for k, v in r["grad_norms"].items()} == pytest.approx(GRAD, rel=1e-5)
    assert {name(k): v for k, v in r["change_norms"].items()} == pytest.approx(CHANGE, rel=1e-5)


def test_dense_flops_per_token_of_the_qwen2_stage():
    from bench import arch

    with open(os.path.join(ROOT, "bench", "configs", "qwen2-7b-pp4.json")) as f:
        config = json.load(f)
    assert arch.of(config).flops_per_token(config, 256) == 10_682_499_072


def test_a_model_type_no_module_implements_fails_at_load(tmp_path):
    from bench import arch
    from bench.cell import load_cell

    config = {**_tiny_dense(), "model_type": "granitemoehybrid"}
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "configs" / "g.json").write_text(json.dumps(config))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "g", "file": "bench/configs/g.json"}],
        "workloads": [{"name": "g.x", "config": "g", "traffic": "x", "chips": 1}]}))
    with pytest.raises(ValueError, match="granitemoehybrid"):
        load_cell("g.x", root=str(tmp_path))
    dense = arch.of({"model_type": "qwen2"})
    for model_type in ("internlm2", "llama"):
        assert arch.of({"model_type": model_type}).leaves is dense.leaves


def test_a_module_of_another_model_type_is_never_loaded(tmp_path, monkeypatch):
    """A module that would fail to import, or that names the same functions,
    sits beside the dense ones: a dense cell never runs it."""
    from bench import arch
    from bench.cell import load_cell

    (tmp_path / "arch").mkdir()
    (tmp_path / "arch" / "broken.py").write_text("raise ImportError('not this one')\n")
    monkeypatch.setattr(arch, "DIRS", arch.DIRS + [str(tmp_path / "arch")])
    cell = load_cell("qwen2-7b-pp4.b16")
    assert arch.of(cell.config).flops_per_token(cell.config, 256) == 10_682_499_072
    with pytest.raises(ImportError, match="not this one"):
        arch.of({"model_type": "broken"})


# --- a toy architecture, added as files alone ---------------------------------

TOY = textwrap.dedent('''
    """A toy hybrid: one period of an SSM-kind position with a SwiGLU MLP
    and an attention position with a dense mixture of SwiGLU experts."""
    import jax
    import jax.numpy as jnp

    from bench import counts
    from bench.reference.blocks import attention, head_loss, matmul, mlp, rms_norm
    from bench.weights import Leaf, padded_vocab

    KEYS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
            "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
            "num_hidden_layers": "n_layers", "vocab_size": "vocab_size",
            "num_local_experts": "n_experts", "num_experts_per_tok": "experts_per_token"}


    def model_fields(c):
        return {f: c[k] for k, f in KEYS.items()}


    def leaves(c):
        d, f, E = c["hidden_size"], c["intermediate_size"], c["num_local_experts"]
        hd = d // c["num_attention_heads"]
        kv = c["num_key_value_heads"] * hd
        n = c["num_hidden_layers"] // 2  # periods
        di, H, N, k = 2 * d, 2 * d // 16, 16, 4
        ssm = {"in_z": (d, di), "in_x": (d, di), "in_B": (d, N), "in_C": (d, N),
               "in_dt": (d, H), "conv_x_w": (k, di), "conv_B_w": (k, N),
               "conv_C_w": (k, N), "out_proj": (di, d)}
        out = {"embed/embedding": Leaf((padded_vocab(c["vocab_size"]), d), d, "normal"),
               "final_norm/scale": Leaf((d,), None, "ones")}
        for name, shape in ssm.items():
            out["blocks/0/mix/" + name] = Leaf(shape, shape[0], "normal", n)
        for name, size in (("conv_x_b", di), ("conv_B_b", N), ("conv_C_b", N)):
            out["blocks/0/mix/" + name] = Leaf((size,), None, "zeros", n)
        for name in ("A_log", "dt_bias"):
            out["blocks/0/mix/" + name] = Leaf((H,), None, "zeros", n, "float32")
        out["blocks/0/mix/D"] = Leaf((H,), None, "ones", n, "float32")
        out["blocks/0/mix/norm_scale"] = Leaf((di,), None, "ones", n)
        out["blocks/0/ffn/gate"] = Leaf((d, f), d, "normal", n)
        out["blocks/0/ffn/up"] = Leaf((d, f), d, "normal", n)
        out["blocks/0/ffn/down"] = Leaf((f, d), f, "normal", n)
        out["blocks/1/mix/wq"] = Leaf((d, d), d, "normal", n)
        out["blocks/1/mix/wk"] = Leaf((d, kv), d, "normal", n)
        out["blocks/1/mix/wv"] = Leaf((d, kv), d, "normal", n)
        out["blocks/1/mix/wo"] = Leaf((d, d), d, "normal", n)
        out["blocks/1/ffn/router"] = Leaf((d, E), d, "normal", n, "float32")
        out["blocks/1/ffn/gate"] = Leaf((E, d, f), d, "normal", n)
        out["blocks/1/ffn/up"] = Leaf((E, d, f), d, "normal", n)
        out["blocks/1/ffn/down"] = Leaf((E, f, d), f, "normal", n)
        for pos in (0, 1):
            for ln in ("ln1", "ln2"):
                out[f"blocks/{pos}/{ln}/scale"] = Leaf((d,), None, "ones", n)
        return out


    def layer_kinds(c):
        return ["ssm", "attn"] * (c["num_hidden_layers"] // 2)


    def _experts(w, x, precision):
        probs = jax.nn.softmax(matmul(x, w["router"], precision), axis=-1)
        ys = [mlp({k: w[k][e] for k in ("gate", "up", "down")}, x, precision)
              for e in range(w["gate"].shape[0])]
        return sum(probs[..., e:e + 1] * y for e, y in enumerate(ys))


    def _loss(c, ws, batch, precision):
        eps = c["rms_norm_eps"]
        emb = ws[("embed/embedding", 0)]
        x = jnp.take(emb, batch["tokens"], axis=0)
        for l, kind in enumerate(layer_kinds(c)):
            pos, period = l % 2, l // 2
            w = {p.split("/", 2)[2]: v for (p, i), v in ws.items()
                 if p.startswith(f"blocks/{pos}/") and i == period}
            h = rms_norm(x, w["ln1/scale"], eps)
            if kind == "ssm":
                h = matmul(jax.nn.silu(matmul(h, w["mix/in_x"], precision)),
                           w["mix/out_proj"], precision)
            else:
                h = attention(c, {k: w["mix/" + k] for k in ("wq", "wk", "wv", "wo")},
                              h, precision)
            x = x + h
            h = rms_norm(x, w["ln2/scale"], eps)
            ffn = {k[4:]: v for k, v in w.items() if k.startswith("ffn/")}
            x = x + (mlp(ffn, h, precision) if kind == "ssm"
                     else _experts(ffn, h, precision))
        return head_loss(c, ws[("final_norm/scale", 0)], emb, x, batch, precision)


    def loss_and_grads(c, weight, batch, on_grad, precision="f32"):
        """The whole toy at once, which is small; weights by (path, instance)."""
        keys = [(p, i) for p, leaf in leaves(c).items() for i in range(leaf.instances)]
        loss, grads = jax.value_and_grad(_loss, argnums=1)(
            c, {k: weight(*k) for k in keys}, batch, precision)
        for k in keys:
            on_grad(k[0], k[1], grads[k])
        return loss


    def flops_per_token(c, seq_len):
        """6 per weight of every matrix (all experts: a toy), plus attention."""
        n = sum(leaf.instances * leaf.shape[-2] * leaf.shape[-1]
                for p, leaf in leaves(c).items() if len(leaf.shape) > 1 and "conv" not in p)
        return 6 * n + counts.attention_flops_per_token(
            c["num_hidden_layers"] // 2, c["num_attention_heads"],
            c["hidden_size"] // c["num_attention_heads"], seq_len)
''')


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    """A root with the toy's module (outside bench/), its configuration,
    traffic and cell; the harness's own files are the checkout's."""
    root = tmp_path_factory.mktemp("toy")
    (root / "arch").mkdir()
    (root / "arch" / "toyhybrid.py").write_text(TOY)
    for d in ("configs", "traffic"):
        (root / "bench" / d).mkdir(parents=True)
    base = _tiny_dense()["assumed"]
    config = {
        "model_type": "toyhybrid", "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2, "num_hidden_layers": 2,
        "vocab_size": 300, "num_local_experts": 4, "num_experts_per_tok": 2,
        "rms_norm_eps": 1e-6, "rope_theta": 1e4,
        "assumed": {**base, "galore": {**base["galore"], "rank": 16},
                    "model": {"family": "hybrid", "attn_every": 2, "attn_offset": 1,
                              "moe_every": 2, "moe_offset": 1, "ssm_state": 16,
                              "ssm_head_dim": 16, "ssm_expand": 2, "ssm_conv": 4,
                              "ssm_chunk": 16, "dtype": "bfloat16"}}}
    with open(os.path.join(ROOT, "bench", "traffic", "c4-seq256-mb16.json")) as f:
        traffic = {**json.load(f), "seq_len": 32, "micro_batch": 4}
    (root / "bench" / "configs" / "toy.json").write_text(json.dumps(config))
    (root / "bench" / "traffic" / "toy.json").write_text(json.dumps(traffic))
    (root / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "toy", "file": "bench/configs/toy.json"}],
        "workloads": [{"name": "toy.toy", "config": "toy", "traffic": "toy",
                       "chips": 1}]}))
    return root


def test_a_toy_architecture_runs_through_the_harness_as_new_files(toy, monkeypatch):
    """The toy's tree has a tuple node (the period's positions) and leaves
    with a leading expert axis. Its weights match the program's hybrid tree
    (benchmark_inputs refuses a mismatch), the program's first steps are
    read through `state`, the GaLore reference takes its steps with a
    projector for each expert, the comparison finds every leaf on both
    sides, and `step_mfu` counts the toy's FLOPs."""
    import jax
    import jax.numpy as jnp

    from bench import arch, check, run
    from bench import weights as bw
    from bench.cell import load_cell
    from bench.metrics import Context, load_reader
    from bench.reference import galore as rg

    monkeypatch.setattr(arch, "DIRS", arch.DIRS + [str(toy / "arch")])
    cell = load_cell("toy.toy", root=str(toy))
    c = cell.config
    tree = jax.eval_shape(lambda: bw.init_params(c, SEED))
    assert isinstance(tree["blocks"], tuple) and len(tree["blocks"]) == 2
    assert tree["blocks"][1]["ffn"]["gate"].shape == (1, 4, 64, 128)

    prog = run.drive(cell, SEED, 0.2, False, jax.devices())
    assert prog["steps"] >= 1 and prog["compiles_in_window"] == 0
    ref = run.reference_readings(cell, SEED, prog["batches"])
    keys = {(p, i) for p, leaf in bw.leaves(c).items() for i in range(leaf.instances)}
    assert set(prog["grad_norms"]) == set(ref["grad_norms"]) == keys
    assert set(prog["change_norms"]) == set(ref["change_norms"]) == keys
    assert ("blocks/1/ffn/gate", 0) in ref["galore"]
    nums = check.numbers(prog, ref)
    assert {"loss1", "loss2", "grad", "grad_elem", "change_galore",
            "change_adam"} <= set(nums)

    one = rg.GaloreReference(c, SEED)
    one.step(prog["batches"][0])
    P = one.P[("blocks/1/ffn/gate", 0)]  # (E, 64, 128) gates: left, a P per expert
    assert P.shape == (4, 64, 16)
    assert jnp.allclose(jnp.swapaxes(P, -1, -2) @ P, jnp.eye(16), atol=1e-5)
    assert not jnp.allclose(P[0], P[1])

    flops = arch.of(c).flops_per_token(c, 32)
    ctx = Context(cell=cell, peaks={"bf16_flops_per_s": 197e12},
                  run={"trace": {"busy_s": 1.0}, "tokens": 128, "window_s": 2.0,
                       "steps": 1}, n_chips=1)
    assert load_reader("step_mfu").read(ctx) == pytest.approx(100 * flops * 64 / 197e12)
