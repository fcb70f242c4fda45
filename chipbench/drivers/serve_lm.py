"""Open-loop requests through the program's continuous-batching engine.

The timed path is ``ContinuousBatchingEngine.submit`` and ``step``: each
step admits queued prompts into free slots (a batch-1 prefill per prompt,
spliced into the slot's cache) and decodes one token for every active
slot. Requests are due on the traffic file's schedule whether or not
earlier ones have finished; a shed submission is retried after the
engine's ``retry_after_s``. Latencies count from the due time. After the
window every request still in flight is served to its end and counted.

Correctness: a sample of finished requests, drawn from the seed with the
longest one in it, goes through the plain reference over prompt + served
tokens; the widest gap by which a served token's logit lies below the
reference's best is compared with the configuration's limit. With
``ctx.control`` the tokens that the float8 control puts first take the
served tokens' place, and the check has to fail."""

from __future__ import annotations

import collections
import contextlib
import functools
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.harness import reference, traffic
from chipbench.harness.core import Check, Context, Outcome
from chipbench.harness.stats import percentile
from chipbench.harness.weights import lm_weights

DRAIN_LIMIT_S = 120.0


def model_config(c: dict):
    """The program's ModelConfig for a dense decoder's published keys."""
    from repro.models.config import ModelConfig

    if c["hidden_act"] != "silu":
        raise ValueError(f"unsupported hidden_act {c['hidden_act']!r}")
    return ModelConfig(
        name=c["name"], family="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], vocab=c["vocab_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c.get("head_dim") or 0,
        d_ff=c["intermediate_size"], sliding_window=c.get("sliding_window")
        or 0, mlp="gated_silu", norm="rms", rope_theta=c["rope_theta"],
        tie_embeddings=c["tie_word_embeddings"], dtype=c["torch_dtype"])


def _engine(c: dict, params):
    from repro.models.api import build_model
    from repro.serve.continuous import ContinuousBatchingEngine

    return ContinuousBatchingEngine(
        build_model(model_config(c)), params,
        n_slots=c["serve"]["slots"], max_seq=c["serve"]["max_seq"])


def warm(eng, lengths: list[int], vocab: int) -> None:
    """Every shape the window uses: each prompt length admitted alone and
    in a batch, every slot active at once and one slot alone."""
    from repro.serve.continuous import Request

    rng = np.random.default_rng(0)
    ids = iter(range(1 << 30, 1 << 31))

    def req(n):
        return Request(rid=next(ids), prompt=rng.integers(
            0, vocab, n, dtype=np.int32), max_new_tokens=2)

    batch = [lengths[i % len(lengths)]
             for i in range(max(eng.n_slots, len(lengths)))]
    for n in batch:
        eng.submit(req(n))
    eng.run_to_completion(max_steps=eng.steps + 4 * len(batch))
    for n in lengths:
        eng.submit(req(n))
        eng.run_to_completion(max_steps=eng.steps + 4)
    if eng.queue or any(s is not None for s in eng.slots):
        raise RuntimeError("warm-up left requests in the engine")
    eng.completed.clear()


class _Flight:
    __slots__ = ("spec", "req", "due", "times", "next_try")

    def __init__(self, spec, req, due):
        self.spec, self.req, self.due = spec, req, due
        self.times: list[float] = []
        self.next_try = due


def run(ctx: Context) -> Outcome:
    from repro.serve.continuous import Request

    c, tr = ctx.cell.config, ctx.cell.traffic
    params = lm_weights(c, ctx.seed)
    eng = _engine(c, params)
    try:
        schedule = traffic.requests(tr, ctx.seconds, ctx.seed,
                                    c["vocab_size"])
        lengths = sorted({len(s.prompt) for s in schedule})
        warm(eng, lengths, c["vocab_size"])
        ctx.setup_done()
        flights = [_Flight(s, Request(rid=s.rid, prompt=s.prompt,
                                      max_new_tokens=s.max_new_tokens),
                           s.due_s) for s in schedule]
        rec = _drive(ctx, eng, flights, tr)
        rec["runtime_classes"] = eng.transfer.runtime.class_summary() \
            if eng.transfer.runtime else None
        device = ctx.read_device()
    finally:
        eng.close()
    del eng
    for leaf in jax.tree.leaves(params):
        leaf.delete()
    del params
    gc.collect()
    return _check(ctx, c, tr, flights, rec, device)


def _drive(ctx, eng, flights, tr, on_close=None) -> dict:
    """The window, then the drain: submit what is due, step while there is
    work, sleep to the next due time otherwise. ``on_close`` is called as
    the window closes."""
    waiting = collections.deque(flights)   # not yet admitted, by due time
    inflight: list[_Flight] = []
    steps, sheds, late = [], 0, []
    t0 = time.perf_counter()
    closed = False
    with contextlib.ExitStack() as stack:
        while True:
            now = time.perf_counter() - t0
            if not closed and now >= ctx.seconds:
                closed = True
                stack.close()  # the traced tail ends with the window
                drain_end = now + DRAIN_LIMIT_S
                if on_close is not None:
                    on_close()
            if closed and (not waiting and not inflight or now > drain_end):
                break
            if not closed:
                ctx.trace_tail(stack, now, tr["trace_seconds"])
            retry = collections.deque()
            while waiting and waiting[0].next_try <= now:
                f = waiting.popleft()
                with ctx.span("bench.submit"):
                    d = eng.submit(f.req)
                if d.admitted:
                    late.append(now - f.due)
                    inflight.append(f)
                else:
                    sheds += 1
                    f.next_try = now + (d.retry_after_s or 0.05)
                    retry.append(f)
            if retry:
                waiting = collections.deque(sorted(
                    [*retry, *waiting], key=lambda f: f.next_try))
            if eng.queue or any(s is not None for s in eng.slots):
                a = time.perf_counter()
                with ctx.span("bench.step"):
                    eng.step()
                b = time.perf_counter()
                admitted, cached = 0, []
                for f in inflight:
                    new = len(f.req.tokens) - len(f.times)
                    if new <= 0:
                        continue
                    if not f.times:
                        admitted += 1
                    f.times += [b - t0] * new
                    if len(f.req.tokens) >= 2:
                        cached.append(len(f.req.prompt)
                                      + len(f.req.tokens) - 2)
                steps.append((a - t0, b - a, admitted, cached))
                inflight = [f for f in inflight if not f.req.done]
            elif waiting or not closed:
                nxt = waiting[0].next_try if waiting else ctx.seconds
                limit = nxt if closed else min(nxt, ctx.seconds)
                with ctx.span("bench.sleep"):
                    time.sleep(max(0.0, limit - (time.perf_counter() - t0)))
    return {"steps": steps, "sheds": sheds, "late_s": late,
            "window_s": ctx.seconds,
            "drain_s": time.perf_counter() - t0 - ctx.seconds}


def _check(ctx, c, tr, flights, rec, device) -> Outcome:
    done = [f for f in flights if f.req.done
            and len(f.req.tokens) == f.req.max_new_tokens]
    failed = len(flights) - len(done)
    vocab = c["vocab_size"]
    bad = [f for f in done if min(f.req.tokens) < 0
           or max(f.req.tokens) >= vocab]
    failed += len(bad)
    rec.update(
        config=c,
        ttft_s=[f.times[0] - f.due for f in done],
        itl_s=[g for f in done for g in np.diff(f.times).tolist()],
        served_tokens=sum(len(f.req.tokens) for f in done))
    t_check = time.perf_counter()
    sample = _sample(done, tr["check"], ctx.seed)
    params = lm_weights(c, ctx.seed)
    gap_fn = jax.jit(functools.partial(reference.lm_gap, c),
                     static_argnames="control")
    rec["check_inputs"] = list(_ref_inputs(sample, c["serve"]["max_seq"]))
    readings = {}
    for who in ("program", "control") if ctx.control else ("program",):
        readings[who] = max((float(gap_fn(params, t, g,
                                          control=who == "control"))
                             for t, g in rec["check_inputs"]), default=0.0)
    del params
    rec["readings"] = readings
    who = "control" if ctx.control else "program"
    checks = [Check("max_logit_gap", readings[who],
                    c["check"]["max_logit_gap"])]
    notes = [f"compared {sum(len(f.req.tokens) for f in sample)} served "
             f"tokens of {len(sample)} requests in "
             f"{time.perf_counter() - t_check:.1f} s; {rec['sheds']} shed "
             f"submissions retried; drain {rec['drain_s']:.1f} s; "
             f"{len(rec['steps'])} steps",
             "ttft_ms p50/p75/p90/p95 " + _pcts(rec["ttft_s"]),
             "itl_ms p50/p95/p99 " + _pcts(rec["itl_s"], (50, 95, 99)),
             "generator late_ms p50/p95/max "
             + _pcts(rec["late_s"], (50, 95, 100))]
    if ctx.control:
        notes.append(f"checked the float8 control in the program's place; "
                     f"the program reads {readings['program']!r}")
    return Outcome(attempted=len(flights), failed=failed, checks=checks,
                   data=rec, device=device, notes=notes)


def _pcts(values, qs=(50, 75, 90, 95)) -> str:
    return " ".join(f"{percentile(values, q) * 1e3:.1f}" for q in qs) \
        if values else "-"


def _sample(done, check: dict, seed: int):
    """The longest finished request, then others in the seed's order,
    until ``min_tokens`` served tokens over ``min_requests`` requests, or
    ``max_requests``."""
    if not done:
        return []
    longest = max(done, key=lambda f: (len(f.req.prompt) + len(f.req.tokens),
                                       -f.spec.rid))
    rest = [done[i] for i in traffic.rng_for(seed, "check").permutation(
        len(done)) if done[i] is not longest]
    out, n = [longest], len(longest.req.tokens)
    for f in rest:
        if len(out) >= check["max_requests"] or (
                n >= check["min_tokens"]
                and len(out) >= check["min_requests"]):
            break
        out.append(f)
        n += len(f.req.tokens)
    return out


def _ref_inputs(sample, width: int):
    """(tokens, target) per request, padded to ``width``: position
    p predicts target[p], -1 where nothing was served."""
    for f in sample:
        prompt, served = f.req.prompt, np.asarray(f.req.tokens, np.int32)
        tokens = np.zeros(width, np.int32)
        seq = np.concatenate([prompt, served])[:width]
        tokens[: len(seq)] = seq
        target = np.full(width, -1, np.int32)
        target[len(prompt) - 1: len(prompt) - 1 + len(served)] = served
        yield jnp.asarray(tokens), jnp.asarray(target)
