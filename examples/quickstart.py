"""Quickstart: build a model from the zoo, train a few steps, then serve.

  PYTHONPATH=src python examples/quickstart.py
"""
import dataclasses

import jax
import jax.numpy as jnp

from repro.configs.registry import smoke_config
from repro.data.synthetic import batch_for_model
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.optim import AdamW, warmup_cosine
from repro.parallel.plan import ParallelPlan, init_state, make_train_step


def main():
    # 1. pick an assigned architecture (reduced config for CPU)
    cfg = dataclasses.replace(smoke_config("phi4-mini-3.8b"),
                              compute_dtype="float32")
    model = build_model(cfg)
    print(f"model: {cfg.name}  params={cfg.param_count():,}")

    # 2. train a few steps.  The ParallelPlan picks the executor — swap
    #    mode="ddp" / mode="pp" on a multi-device mesh for the explicit
    #    HFReduce or pipelined paths (launch/train.py --parallel).
    opt = AdamW(lr=warmup_cosine(3e-3, 2, 20), param_dtype="float32")
    params = model.init(jax.random.PRNGKey(0))
    mesh = make_mesh((1, 1), ("data", "model"))
    plan = ParallelPlan(mode="gspmd", tp=1, fsdp=False,
                        batch_axes=("data",))
    state = init_state(plan, opt, params, mesh)
    step = make_train_step(plan, model, opt, mesh,
                           params_template=params, donate=True)
    for i in range(10):
        batch = {k: jnp.asarray(v) for k, v in
                 batch_for_model(cfg, "train", i, 4, 64).items()}
        state, metrics = step(state, batch)
        print(f"  step {i}: loss={float(metrics['loss']):.4f}")

    # 3. serve through the chunk-oriented SeqState API: the prompt is
    #    one fresh chunk, every decode step a T=1 chunk (any chunking
    #    in between yields the same tokens)
    params = state["params"]
    pb = {k: jnp.asarray(v) for k, v in
          batch_for_model(cfg, "prefill", 0, 2, 16).items()}
    tokens, positions, embeds = model.prompt_inputs(params, pb)
    b, s = positions.shape
    seq = model.init_seq_state(params, s + 8, batch=pb, batch_size=b)
    fwd = jax.jit(model.forward, static_argnames=("fresh",))
    seq, logits = fwd(params, seq, tokens, positions, embeds=embeds,
                      fresh=True)
    toks = jnp.argmax(logits, -1).astype(jnp.int32)
    out = [toks]
    for i in range(7):
        pos = jnp.full((b, 1), s + i, jnp.int32)
        seq, logits = fwd(params, seq, toks[:, None], pos)
        toks = jnp.argmax(logits, -1).astype(jnp.int32)
        out.append(toks)
    print("generated:", jnp.stack(out, 1).tolist())


if __name__ == "__main__":
    main()
