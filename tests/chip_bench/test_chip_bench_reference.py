"""The plain references against the program, at reduced widths on the CPU,
both in float32: the same weights give the same logits, loss and
gradients, so a gap on the chip is the program's precision or a fault."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np

import chip_bench_support as sup
import programs
import weights
from reference import qwen3, xlstm


def _conf(name):
    conf = json.loads((sup.BENCH / "configs" / f"{name}.json").read_text())
    conf.update(sup.REDUCED_CONFIGS[name])
    return conf


def _program(conf):
    from repro.models import build_model

    cfg = dataclasses.replace(programs.model_config(conf), dtype="float32",
                              param_dtype="float32")
    return build_model(cfg)


def test_qwen3_reference_matches_the_program_forward():
    conf = _conf("qwen3-4b")
    model = _program(conf)
    w = weights.make(conf, sup.bench.jax_key(3), "float32")
    assert weights.shapes_match(w, model.abstract_params())
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, conf["vocab_size"], 24), jnp.int32)
    with jax.default_matmul_precision("highest"):
        prog = model.forward(w, {"tokens": tokens[None]})[0][0]
        ref = qwen3.logits(conf, w, tokens)
    np.testing.assert_allclose(np.asarray(prog), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_qwen3_readings_pick_the_best_and_the_target():
    conf = _conf("qwen3-4b")
    w = weights.make(conf, sup.bench.jax_key(4), "float32")
    tokens = jnp.arange(10, dtype=jnp.int32)
    lg = qwen3.logits(conf, w, tokens)
    best, tgt, top = qwen3.readings(conf, w, tokens, jnp.argmax(lg, axis=1).astype(jnp.int32))
    np.testing.assert_allclose(np.asarray(best), np.asarray(tgt))
    assert np.array_equal(np.asarray(top), np.asarray(jnp.argmax(lg, axis=1)))


def test_xlstm_reference_matches_the_program_loss_and_gradients():
    from repro.train.steps import cross_entropy

    conf = _conf("xlstm-350m")
    model = _program(conf)
    w = weights.make(conf, sup.bench.jax_key(5), "float32")
    assert weights.shapes_match(w, model.abstract_params())
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(0, conf["vocab_size"], (2, 32)), jnp.int32)
    labels = jnp.roll(tokens, -1, axis=1)

    def prog_loss(p):
        return cross_entropy(model.forward(p, {"tokens": tokens})[0], labels)

    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(prog_loss)(w)
        lr, gr = jax.value_and_grad(lambda p: xlstm.loss(conf, p, tokens, labels))(w)
    np.testing.assert_allclose(float(lp), float(lr), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(gp), jax.tree_util.tree_leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-3 * float(jnp.max(jnp.abs(b))))


def test_xlstm_compare_reads_zero_for_itself_and_one_for_a_frozen_state():
    conf = _conf("xlstm-350m")
    rows = sup.bench.load_module(sup.BENCH / "traffic.py").TrainRows(6, conf["vocab_size"], 16, 2)
    opt = sup.bench.load_json("traffic", "train")["optimizer"]
    opt = dict(opt, warmup_steps=1)
    with jax.default_matmul_precision("highest"):
        ref = xlstm.train_readings(conf, opt, lambda: weights.make(conf, sup.bench.jax_key(6), "float32"),
                                   [rows.batch(s) for s in range(3)])
    same = xlstm.compare(ref, ref)
    assert same == {"loss_gap": 0.0, "grad_gap": 0.0, "update_gap": 0.0}
    frozen = dict(ref, change=[0.0] * len(ref["change"]))
    assert xlstm.compare(frozen, ref)["update_gap"] == 1.0
