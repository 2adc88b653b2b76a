"""The float32 reference against the system's own models, at a small
size on the CPU, for both configurations: same weights, same answers."""
import dataclasses
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny_cells  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import harness  # noqa: E402
from bench.reference import decoder, weights  # noqa: E402


def system(name):
    from repro.models import build_model
    cfg = tiny_cells.config(name)
    spec = harness.decoder_spec(cfg)
    mcfg = dataclasses.replace(harness.model_config(cfg),
                               compute_dtype="float32", attn_impl="ref",
                               norm_impl="ref")
    return spec, build_model(mcfg)


@pytest.mark.parametrize("name", ["phi4-mini", "gpt2-medium"])
def test_weights_fit_the_model_layout(name):
    spec, model = system(name)
    want = jax.tree_util.tree_map(lambda x: x.shape,
                                  weights.param_shapes(spec))
    have = jax.tree_util.tree_map(lambda x: x.shape, model.param_shapes())
    assert want == have


@pytest.mark.parametrize("name", ["phi4-mini", "gpt2-medium"])
def test_prefill_logits_match(name):
    spec, model = system(name)
    params = weights.make_params(spec, 2**35 + 9, "float32")
    toks = np.random.default_rng(0).integers(0, spec.vocab, (1, 24),
                                             dtype=np.int32)
    state = model.init_seq_state(params, 24, batch_size=1, dtype="float32")
    pos = jnp.arange(24, dtype=jnp.int32)[None]
    _, got = model.forward(params, state, jnp.asarray(toks), pos, fresh=True)
    with jax.default_matmul_precision("highest"):
        want = decoder.row_logits(spec, params, jnp.asarray(toks[0]),
                                  jnp.asarray([23]))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("name", ["phi4-mini", "gpt2-medium"])
def test_loss_and_gradients_match(name):
    spec, model = system(name)
    params = weights.make_params(spec, 17, "float32")
    rng = np.random.default_rng(1)
    toks = rng.integers(0, spec.vocab, (4, 17), dtype=np.int32)
    batch = {"tokens": jnp.asarray(toks[:, :-1]),
             "labels": jnp.asarray(toks[:, 1:])}
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(
            lambda p: model.loss(p, batch)[0])(params)
    lr, gr = decoder.grads(spec, params, batch["tokens"], batch["labels"], 2)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(gp),
                    jax.tree_util.tree_leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-6)


def test_paged_engine_serves_what_the_reference_predicts():
    """Prefill, K/V in the pool and paged decode, float32 end to end:
    every served greedy token is the reference's best."""
    from repro.serving import ServingEngine
    from bench.kinds.serve_open_loop import widest_gap
    spec, model = system("phi4-mini")
    params = weights.make_params(spec, 5, "float32")
    eng = ServingEngine(model, params, n_blocks=64, block_size=16,
                        max_slots=2, pool_dtype="float32")
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, spec.vocab, n, dtype=np.int32)
               for n in (20, 37)]
    rids = [eng.submit(p, 6) for p in prompts]
    out = eng.run()
    gap = widest_gap(spec, params, [(p, list(out[r]))
                                    for p, r in zip(prompts, rids)], 48, 8)
    assert gap < 1e-4


def test_fp8_control_is_coarser():
    spec, _ = system("phi4-mini")
    params = weights.make_params(spec, 3, "float32")
    toks = jnp.asarray(np.random.default_rng(3).integers(
        0, spec.vocab, 32, dtype=np.int32))
    rows = jnp.arange(32)
    ref = decoder.row_logits(spec, params, toks, rows)
    low = decoder.row_logits(spec, params, toks, rows, "fp8")
    err = float(jnp.max(jnp.abs(ref - low)))
    assert 1e-4 < err < 1.0
