"""The comparison that decides `correct`, on the CPU at a tiny size.

A tiny dense cell (2 layers, d 128, GQA with one K/V head, q/k/v bias,
rank 32, seq 32 x 4 rows) runs through the whole harness except its look
for a chip: the program's `train_loop` with the benchmark's weights and
rows, the set-up readings, the window and the float32 reference. The
program agrees with the reference within the limits below; the reference
in float8 put in the program's place (the control) does not, nor does a
run whose timed path is broken underneath.

The limits are this size's own, set from the readings at this test's seed
(below) as the chip's are set from theirs (bench/limits/): above the
program's reading, below the control's and the faults'.
"""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SEED = 123456789012
# readings at this seed, program / float8 control / half batch / state
# unchanged / embedding update skipped (in the program) / second moment
# read 4x (in the reference): loss1 6.8e-5 / 7.3e-4 / 1.6e-2 / 6.8e-5 /
# 6.8e-5 / 0, loss2 8.6e-4 / 1.7e-3 / 2.1e-2 / 1.0e-3 / 2.4e-3 / 4.5e-5,
# grad 5.9e-3 / 1.8e-2 / 0.23 / 1 / 5.9e-3 / 0, grad_elem 1.9e-2 / 0.20 /
# 0.73 / 1 / 1.9e-2 / 0, change_galore 2.2e-2 / 1.0e-2 / 2.9e-2 / 1 /
# 2.1e-2 / 8.5e-3, change_adam 5.8e-2 / 4.1e-2 / 6.6e-2 / 1 / 1 / 0.50
LIMITS = {"loss1": 1e-4, "loss2": 2e-3, "grad": 2e-2, "grad_elem": 0.06,
          "change_galore": 0.1, "change_adam": 0.15}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A root with BENCHMARK.json and the tiny cell's files."""
    root = tmp_path_factory.mktemp("tiny")
    for d in ("configs", "traffic", "limits"):
        (root / "bench" / d).mkdir(parents=True)
    with open(os.path.join(ROOT, "bench", "configs", "qwen2-7b-pp4.json")) as f:
        config = json.load(f)
    config.update(hidden_size=128, intermediate_size=256, num_attention_heads=4,
                  num_key_value_heads=1, num_hidden_layers=2, vocab_size=500)
    config["assumed"]["galore"]["rank"] = 32
    # weights of a 128-wide model are 7x a 3584-wide one's: a step of 1e-2
    # keeps the GaLore updates a few bf16 spacings wide, as 1e-3 does there
    config["assumed"]["train"]["lr"] = 1e-2
    with open(os.path.join(ROOT, "bench", "traffic", "c4-seq256-mb16.json")) as f:
        traffic = json.load(f)
    traffic.update(seq_len=32, micro_batch=4)
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(config))
    (root / "bench" / "traffic" / "tiny.json").write_text(json.dumps(traffic))
    (root / "bench" / "limits" / "tiny.tiny.json").write_text(
        json.dumps({"limits": LIMITS}))
    (root / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "tiny", "file": "bench/configs/tiny.json"}],
        "workloads": [{"name": "tiny.tiny", "config": "tiny", "traffic": "tiny",
                       "chips": 1}]}))
    from bench.cell import load_cell

    return load_cell("tiny.tiny", root=str(root)), str(root)


def _run(cell, monkeypatch=None, fault=None):
    import jax

    from bench import run

    if fault is not None:
        import repro.launch.train as T

        monkeypatch.setattr(T, "make_train_step", _broken(T.make_train_step, fault))
    return run.drive(cell, SEED, 0.2, False, jax.devices())


def _broken(make_train_step, fault):
    """The program's train step with a fault planted in it."""

    def make(cfg, tc, rules=None):
        step, opt = make_train_step(cfg, tc, rules)

        def unchanged(params, opt_state, batch):
            _, _, metrics = step(params, opt_state, batch)
            return params, opt_state, metrics

        def half_batch(params, opt_state, batch):
            return step(params, opt_state,
                        {k: v[: v.shape[0] // 2] for k, v in batch.items()})

        def embed_update_skipped(params, opt_state, batch):
            new, opt_state, metrics = step(params, opt_state, batch)
            return {**new, "embed": params["embed"]}, opt_state, metrics

        return {"unchanged": unchanged, "half_batch": half_batch,
                "embed_update_skipped": embed_update_skipped}[fault], opt

    return make


def _verdict(cell, root, prog, ref):
    from bench import check

    return check.verdict(check.numbers(prog, ref), check.limits(cell.name, root))


@pytest.fixture(scope="module")
def sound(tiny):
    from bench import run

    cell, _ = tiny
    prog = _run(cell)
    ref = run.reference_readings(cell, SEED, prog["batches"])
    return prog, ref


def test_program_agrees_with_the_reference(tiny, sound):
    cell, root = tiny
    prog, ref = sound
    ok, out = _verdict(cell, root, prog, ref)
    assert ok, out
    assert prog["steps"] >= 1 and prog["compiles_in_window"] == 0


def test_float8_control_fails(tiny, sound):
    from bench import run

    cell, root = tiny
    prog, ref = sound
    control = run.reference_readings(cell, SEED, prog["batches"], precision="fp8")
    ok, out = _verdict(cell, root, control, ref)
    assert not ok, out
    # the gradient element by element sees what its norm averages away
    assert out["grad_elem"]["value"] > 3 * LIMITS["grad_elem"], out


@pytest.mark.parametrize("fault", ["embed_update_skipped", "v_scale_x4"])
def test_reference_with_a_planted_fault_fails(tiny, sound, fault):
    """The reference with a fault in its full-shape 8-bit Adam update, put in
    the program's place, is not correct."""
    from bench import run

    cell, root = tiny
    prog, ref = sound
    broken = run.reference_readings(cell, SEED, prog["batches"], fault=fault)
    ok, out = _verdict(cell, root, broken, ref)
    assert not ok and out["change_adam"]["value"] > LIMITS["change_adam"], out


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "embed_update_skipped"])
def test_broken_step_is_not_correct(tiny, sound, monkeypatch, fault):
    cell, root = tiny
    _, ref = sound
    prog = _run(cell, monkeypatch, fault)
    ok, out = _verdict(cell, root, prog, ref)
    assert not ok, out


def test_reference_layer_walk_matches_whole_model_gradients(tiny):
    """The reference's one-layer-at-a-time backward gives the gradients of
    the whole model written as one function."""
    import jax
    import jax.numpy as jnp

    from bench import arch
    from bench import weights as bw
    from bench.data import TokenStream
    from bench.reference import galore as rg
    from bench.reference.blocks import head_loss

    cell, _ = tiny
    c = cell.config
    dense = arch.of(c)
    ref = rg.GaloreReference(c, SEED)
    batch = TokenStream(cell.traffic, c["vocab_size"], cell.rows, SEED).batch(0)
    names = [p for p in dense.SHORT if p in bw.leaves(c)]
    params = {"embed": ref.weight("embed/embedding", 0),
              "final": ref.weight("final_norm/scale", 0),
              "layers": [{dense.SHORT[p]: ref.weight(p, l) for p in names}
                         for l in range(c["num_hidden_layers"])]}

    def whole(p):
        x = jnp.take(p["embed"], batch["tokens"], axis=0)
        for w in p["layers"]:
            x = dense.layer(c, w, x)
        return head_loss(c, p["final"], p["embed"], x, batch)

    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(whole)(params)
        got = {}
        loss = dense.loss_and_grads(
            c, ref.weight, batch, lambda p, l, g: got.setdefault((p, l), g))
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    tol = dict(rtol=2e-4, atol=1e-7)
    assert jnp.allclose(got[("embed/embedding", 0)], want["embed"], **tol)
    assert jnp.allclose(got[("final_norm/scale", 0)], want["final"], **tol)
    for l, w in enumerate(want["layers"]):
        for name, g in w.items():
            assert jnp.allclose(got[(dense.PATH[name], l)], g, **tol), (l, name)
    # every gradient is handed on once, and every leaf of every layer has one
    assert set(got) == {(p, i) for p, leaf in bw.leaves(c).items()
                        for i in range(leaf.instances)}


def test_reference_subspace_is_the_top_singular_subspace():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench.reference import galore as rg

    A = jax.random.normal(jax.random.PRNGKey(0), (96, 300))
    A = A * jnp.exp(-jnp.arange(96) / 30.0)[:, None]  # a decaying spectrum
    P = rg.top_subspace(A, 24, jax.random.PRNGKey(1))
    U, s, _ = np.linalg.svd(np.asarray(A, np.float64))
    captured = np.linalg.norm(np.asarray(P).T @ np.asarray(A)) ** 2
    assert captured / np.sum(s[:24] ** 2) == pytest.approx(1.0, abs=1e-5)
    assert np.allclose(np.asarray(P).T @ np.asarray(P), np.eye(24), atol=1e-5)
