"""Host spans and compile counters (repro/obs.py) around the training loop,
and the int8 codec's named scopes in the compiled HLO."""
import re

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import GaLoreConfig, TrainConfig
from repro.core.galore import galore
from repro.launch.train import RunConfig, train_loop
from repro.optim.adam import scale_by_adam
from repro.quant import QuantPolicy


def _children(spans, parent):
    return [s for s in spans if s[1] == parent]


def test_train_loop_span_tree(tmp_path, capsys):
    run = RunConfig(arch="llama_60m", smoke=True, steps=2, batch_per_host=2,
                    seq_len=16, ckpt_dir=str(tmp_path), ckpt_every=0,
                    log_every=1)
    tc = TrainConfig(optimizer="adamw", lr=1e-3, total_steps=2, warmup_steps=1)
    rec = obs.Recorder().install()
    try:
        train_loop(run, tc)
    finally:
        rec.uninstall()
    spans = rec.spans
    for i, (name, parent, start, end) in enumerate(spans):
        assert end is not None and end >= start, (name, start, end)
        if parent is not None:
            assert parent < i
            p_start, p_end = spans[parent][2], spans[parent][3]
            if not name.startswith("compile:"):  # JAX's clock, shifted
                assert p_start <= start and end <= p_end, (name, spans[parent])
    roots = [s[0] for s in _children(spans, None)
             if not s[0].startswith("compile:")]
    assert roots == ["setup.shardings", "setup.programs", "setup.state",
                     "setup.restore", "train.step", "train.step"], roots
    steps = [i for i, s in enumerate(spans) if s[0] == "train.step"]
    for i in steps:
        kids = [s[0] for s in _children(spans, i)]
        assert kids[:2] == ["train.data", "train.dispatch"], kids
        assert "train.log" in kids and "train.ckpt" in kids, kids
    dispatch0 = next(i for i, s in enumerate(spans)
                     if s[0] == "train.dispatch" and s[1] == steps[0])
    compiles = [s[0] for s in _children(spans, dispatch0)]
    assert any(c.startswith("compile:") for c in compiles), compiles
    assert set(rec.counters) == {"cache_hits", "cache_misses",
                                 "cache_retrieval_s"}
    # the step time is read between two blocking loss reads, not per dispatch
    log = capsys.readouterr().out
    assert re.search(r"\[train\] step 0 loss [\d.]+\n", log), log
    assert re.search(r"\[train\] step 1 loss [\d.]+ \(\d+ ms/step\)", log), log

    # uninstalled: spans still annotate, and nothing is recorded
    n = len(spans)
    with obs.span("train.data") as got, obs.step_span(7) as got_step:
        jax.jit(lambda x: x * 3)(jnp.ones(5)).block_until_ready()
    assert got is None and got_step is None and len(rec.spans) == n


def test_int8_codec_ops_carry_named_scopes():
    qp = QuantPolicy(moments="int8", min_quant_size=1)
    opt = galore(scale_by_adam(), GaLoreConfig(rank=4, update_freq=5,
                                               scale=0.25, quant=qp),
                 fused_adam=True, b1=0.9, b2=0.999, eps=1e-8)
    params = {"wide": jnp.ones((8, 130))}  # int8 compact moments (4, 130)
    state = opt.init(params)
    text = jax.jit(opt.update).lower(params, state, params).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    searches = [n for n in names if "searchsorted" in n]
    assert searches and all("/int8_quantize/" in n for n in searches), searches
    assert any(re.search(r"/int8_dequantize/.*gather", n) for n in names), names
