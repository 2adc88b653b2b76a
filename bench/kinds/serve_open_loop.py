"""Open-loop serving through ``ServingEngine`` (the paged path of
``repro.launch.serve``).

Set-up makes the weights from the seed on the device, builds the engine
from the configuration's ``engine`` block and warms every shape the
window will use: one request of each prompt capacity bucket from the
shortest to the longest prompt, each alone, which compiles the prefill
at every bucket and the decode step at every block-table width the
traffic can reach; then one request of each distinct prompt length of
the schedule, because the engine compiles its prefill slice, pad and
block scatter anew for every prompt length (Open questions in
PERF.md).  The warm-up prompts are random tokens, not the schedule's.

The window submits each request when it is due (``t_submit`` is its due
time, so queueing counts), steps the engine whenever it has work, and
notes when each token reaches the caller: the moment ``step()`` returns
it.  After the window it keeps stepping, without new arrivals, until
every request due in the window has its first token (one that never
gets it is ``failed``).  ``tpot_p95_ms`` is over every gap between two
tokens of a request.

``correct``: a sample of finished requests drawn from the seed, the
longest among them, is run through the float32 reference with the
tokens the engine served; the number compared is the widest gap by
which a served token's logit lies below the reference's best.
"""
from __future__ import annotations

import math
import time

import numpy as np

from bench import traffic_gen
from bench.harness import decoder_spec, model_config
from bench.reference import weights

WAIT_FIRST_TOKEN_S = 60.0


def _pow2_at_least(n: int, floor: int) -> int:
    w = floor
    while w < n:
        w *= 2
    return w


def warm_prompt_lengths(traffic: dict, block_size: int) -> list[int]:
    """One prompt per capacity bucket the traffic's range reaches: the
    bucket sizes themselves, clipped to the range."""
    lo, hi = traffic["prompt"]["min"], traffic["prompt"]["max"]
    out, cap = [], _pow2_at_least(lo, block_size)
    while True:
        out.append(min(max(cap, lo), hi))
        if cap >= hi:
            return out
        cap *= 2


def p95(xs) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), 95))


class Runner:
    def __init__(self, ctx):
        self.ctx = ctx
        self.config = ctx.cell.config
        self.traffic = ctx.cell.traffic
        self.spec = decoder_spec(self.config)

    # ------------------------------------------------------------ set-up

    def setup(self) -> None:
        from repro.models import build_model
        from repro.serving import ServingEngine
        from repro.telemetry import now

        t0 = now()
        cfg = model_config(self.config)
        model = build_model(cfg)
        weights.check_layout(self.spec, model, cfg.compute_dtype)
        params = weights.make_params(self.spec, self.ctx.seed,
                                     cfg.compute_dtype)
        _block(params)
        t1 = now()
        eng = self.config["engine"]
        self.engine = ServingEngine(
            model, params, n_blocks=eng["n_blocks"],
            block_size=eng["block_size"], max_slots=eng["max_slots"],
            prefill_chunk=eng["prefill_chunk"], pool_dtype=eng["pool_dtype"])
        self.params = params
        c0 = len(self.ctx.compiles.events)
        rng = np.random.default_rng(0)
        for n in warm_prompt_lengths(self.traffic, eng["block_size"]):
            self.engine.submit(rng.integers(0, self.spec.vocab, n,
                                            dtype=np.int32), 3)
            self.engine.run()
        t2 = now()
        c1 = len(self.ctx.compiles.events)
        self.schedule = traffic_gen.open_loop(
            self.traffic, self.ctx.seed, self.ctx.seconds, self.spec.vocab)
        lengths = sorted({len(r.prompt) for r in self.schedule})
        for n in lengths:
            self.engine.submit(rng.integers(0, self.spec.vocab, n,
                                            dtype=np.int32), 2)
        self.engine.run()
        self.engine.cache.drop_prefixes()
        t3 = now()
        ev = self.ctx.compiles.events
        self.setup_record = {
            "weights_s": t1 - t0, "bucket_warmup_s": t2 - t1,
            "bucket_compiles": c1 - c0,
            "bucket_compile_s": sum(d for _, d in ev[c0:c1]),
            "length_warmup_s": t3 - t2, "distinct_lengths": len(lengths),
            "length_compiles": len(ev) - c1,
            "length_compile_s": sum(d for _, d in ev[c1:])}

    # ------------------------------------------------------------ window

    def window(self, seconds: float) -> dict:
        from repro.telemetry import now
        ctx, eng, sched = self.ctx, self.engine, self.schedule
        reqs: list = []            # engine Request objects, schedule order
        times: list = []           # per request: token arrival times
        steps: list = []           # (t_start, t_end, decode keys list)
        late: list = []
        nxt = 0
        t0 = now()
        deadline = t0 + seconds + WAIT_FIRST_TOKEN_S
        while True:
            t = now()
            ctx.poll()
            while nxt < len(sched) and sched[nxt].due <= t - t0:
                r = sched[nxt]
                with ctx.span("submit"):
                    eng.submit(r.prompt, r.max_new_tokens,
                               t_submit=t0 + r.due)
                reqs.append(eng._queue[-1])
                times.append([])
                late.append(t - t0 - r.due)
                nxt += 1
            if t - t0 >= seconds:
                ctx.stop_trace()
                if nxt == len(sched) and all(times) or t > deadline:
                    break
            busy = (eng._queue or eng._job is not None
                    or any(s is not None for s in eng._slots))
            if not busy:
                with ctx.span("idle"):
                    if nxt < len(sched):
                        wait = t0 + sched[nxt].due - now()
                    else:
                        wait = t0 + seconds - now()
                    if wait > 0:
                        time.sleep(min(wait, 0.002))
                continue
            before = [len(r.tokens) for r in reqs]
            ts = now()
            with ctx.span("engine_step"):
                eng.step()
            te = now()
            keys = []
            for i, r in enumerate(reqs):
                got = len(r.tokens) - before[i]
                if got <= 0:
                    continue
                times[i].extend([te] * got)
                # one decode per running request per step: it attends the
                # prompt plus every token it had (+1 on its first step,
                # when the prefill's token was written by this decode)
                keys.append(len(r.prompt) + max(before[i], 1))
            steps.append((ts, te, keys))
        # served tokens, as the engine holds them (a finished request
        # keeps exactly max_new_tokens)
        served = [list(r.tokens) for r in reqs]
        firsts = [ts_[0] if ts_ else None for ts_ in times]
        return {
            "t0": t0, "seconds": seconds,
            "due": [t0 + r.due for r in sched[:len(reqs)]],
            "prompt_len": [len(r.prompt) for r in sched[:len(reqs)]],
            "max_new": [r.max_new_tokens for r in sched[:len(reqs)]],
            "first": firsts, "token_times": times, "served": served,
            "prompts": [r.prompt for r in sched[:len(reqs)]],
            "steps": steps, "late_s": late,
            "attempted": len(sched),
            "failed": sum(f is None for f in firsts) + len(sched) - len(reqs),
            "setup": self.setup_record,
        }

    def end_to_end(self, rec: dict) -> dict:
        gaps = [(b - a) * 1e3 for ts in rec["token_times"]
                for a, b in zip(ts, ts[1:])]
        return {"tpot_p95_ms": p95(gaps)} if gaps else {}

    def release(self) -> None:
        del self.engine, self.params

    # ------------------------------------------------------------ check

    def check(self, rec: dict) -> dict:
        """Widest gap, over every served token of the sample, between the
        reference's best logit and the served token's logit."""
        sample = pick_sample(rec, self.ctx.seed, self.traffic["check"])
        if not sample:
            return {"logit_gap": {"value": math.inf,
                                  "limit": self.limit("logit_gap")}}
        widest = served_gap(self.spec, self.ctx.seed, self.traffic,
                            [(rec["prompts"][i], rec["served"][i])
                             for i in sample])
        return {"logit_gap": {"value": widest,
                              "limit": self.limit("logit_gap")}}

    def limit(self, name: str) -> float:
        return self.traffic["limits"][self.config["name"]][name]


def pick_sample(rec: dict, seed: int, check: dict) -> list[int]:
    """Indices of finished requests to check: the longest (prompt plus
    output) and others drawn from the seed, up to ``check["requests"]``."""
    done = [i for i, s in enumerate(rec["served"])
            if len(s) >= rec["max_new"][i]]
    if not done:
        return []
    longest = max(done, key=lambda i: rec["prompt_len"][i] + rec["max_new"][i])
    rest = [i for i in done if i != longest]
    rng = np.random.default_rng([seed & 0xFFFFFFFFFFFFFFFF, 7])
    k = min(check["requests"] - 1, len(rest))
    return [longest] + sorted(rng.choice(rest, k, replace=False).tolist())


def served_gap(spec, seed: int, traffic: dict, pairs, precision="f32",
               control: bool = False) -> float:
    """Widest reference-logit gap over the served tokens of ``pairs``
    [(prompt, served tokens)], with the reference's own weights from
    ``seed``; every sequence padded to the traffic's longest, so one
    compiled program serves every request."""
    params = weights.make_params(spec, seed, "bfloat16")
    return widest_gap(spec, params, pairs,
                      traffic["prompt"]["max"] + traffic["output"]["max"],
                      traffic["output"]["max"], precision, control)


def widest_gap(spec, params, pairs, length: int, rows_max: int,
               precision="f32", control: bool = False) -> float:
    """max over served tokens of (reference's best logit - reference's
    logit of the token).  ``control``: the token is instead the one that
    the reference at ``precision`` puts first at that position."""
    import jax
    import jax.numpy as jnp

    from bench.reference import decoder

    @jax.jit
    def gaps(params, tokens, rows, targets):
        ref = decoder.row_logits(spec, params, tokens, rows)
        if control:
            low = decoder.row_logits(spec, params, tokens, rows, precision)
            targets = jnp.argmax(low, axis=-1)
        got = jnp.take_along_axis(ref, targets[:, None], axis=-1)[:, 0]
        return jnp.max(ref, axis=-1) - got

    widest = 0.0
    for prompt, served in pairs:
        seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
        toks = np.zeros(length, np.int32)
        toks[:len(seq)] = seq
        n = len(served)
        rows = np.full(rows_max, len(prompt) - 1, np.int32)
        rows[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
        tg = np.zeros(rows_max, np.int32)
        tg[:n] = served
        g = np.asarray(gaps(params, jnp.asarray(toks), jnp.asarray(rows),
                            jnp.asarray(tg)))[:n]
        widest = max(widest, float(g.max()))
    return widest


def _block(tree) -> None:
    import jax
    jax.block_until_ready(tree)
