"""The comparison that decides ``correct`` catches a broken timed path
and the lower-precision control, at a size a CPU test can hold.

Each run drives the whole harness except its look for a chip.  The
limits here are set for this tiny size (the committed ones are for the
cells' own sizes): each sits well above what the sound run reads and
below what the fault reads.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny_cells  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import harness, traffic_gen  # noqa: E402

SERVE_LIMITS = {"logit_gap": 0.05}
TRAIN_LIMITS = {"loss_gap": 5e-3, "grad_norm_gap": 0.05,
                "update_norm_gap": 0.05}


def alter_tokens(kind):
    """A served token altered where it is produced: the fourth token of
    every request is replaced after the engine picks it."""
    setup = kind.Runner.setup

    def broken_setup(self):
        setup(self)
        step = self.engine.step

        def bad_step():
            n = step()
            for r in self.engine._slots:
                if r is not None and len(r.tokens) == 4:
                    r.tokens[-1] = (r.tokens[-1] + 97) % self.spec.vocab
            return n
        self.engine.step = bad_step
    kind.Runner.setup = broken_setup


def test_serving_sound_and_altered_token():
    cell = tiny_cells.cell("phi4-mini", "chat", limits=SERVE_LIMITS)
    sound = tiny_cells.run(cell, seed=21, seconds=2.0)
    assert sound["correct"]
    bad = tiny_cells.run(cell, seed=21, seconds=2.0, patch=alter_tokens)
    assert not bad["correct"]
    assert bad["checks"]["logit_gap"]["value"] > \
        10 * sound["checks"]["logit_gap"]["value"]


def unchanged_state(kind):
    build = kind.Runner.build_step

    def broken(self):
        step = build(self)

        def bad(state, batch):
            import jax
            import jax.numpy as jnp
            # the step donates its state: run it on a copy, keep the input
            _, met = step(jax.tree_util.tree_map(jnp.copy, state), batch)
            return state, met
        return bad
    kind.Runner.build_step = broken


def half_batch(kind):
    build = kind.Runner.build_step

    def broken(self):
        step = build(self)

        def bad(state, batch):
            return step(state, {k: v[:v.shape[0] // 2]
                                for k, v in batch.items()})
        return bad
    kind.Runner.build_step = broken


@pytest.mark.parametrize("fault", [None, unchanged_state, half_batch])
def test_training_faults(fault):
    cell = tiny_cells.cell("gpt2-medium", "train-1chip", limits=TRAIN_LIMITS)
    res = tiny_cells.run(cell, seed=33, seconds=0.5, patch=fault)
    assert res["correct"] == (fault is None), res["checks"]


def test_serving_control_fails():
    """The reference computed in fp8, put in the program's place: the
    tokens it puts first lie far below the float32 reference's best."""
    from bench.kinds.serve_open_loop import widest_gap
    from bench.reference import weights
    cfg = tiny_cells.config("phi4-mini")
    spec = harness.decoder_spec(cfg)
    params = weights.make_params(spec, 4, "bfloat16")
    rng = np.random.default_rng(4)
    pairs = [(rng.integers(0, spec.vocab, 40, dtype=np.int32),
              list(rng.integers(0, spec.vocab, 12)))]
    low = widest_gap(spec, params, pairs, 64, 12, "fp8", control=True)
    same = widest_gap(spec, params, pairs, 64, 12, "f32", control=True)
    assert same == 0.0
    assert low > SERVE_LIMITS["logit_gap"]


def test_training_control_fails():
    from bench.kinds.train_steps import compare, reference_readings
    cfg = tiny_cells.config("gpt2-medium")
    spec = harness.decoder_spec(cfg)
    tr = tiny_cells.traffic("train-1chip")
    batches = traffic_gen.train_batches(tr, 9, 3, 8, spec.vocab)
    ref = reference_readings(spec, cfg["optimizer"], 9, batches, 4)
    low = reference_readings(spec, cfg["optimizer"], 9, batches, 4, "fp8")
    checks = compare(low, ref, TRAIN_LIMITS)
    assert any(c["value"] > c["limit"] for c in checks.values()), checks
