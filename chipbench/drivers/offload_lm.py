"""Decode steps of a MoE model whose routed experts live in host memory,
through the program's continuous-batching engine.

The timed path is ``ContinuousBatchingEngine.step`` driving the model of
``repro.models.moonlight.ExpertOffload``: per step and MoE layer,
attention and the router on the chip, the routed expert ids back to the
host, the distinct routed experts streamed from host memory by
``HostStreamingExecutor.stream_routed`` on a ``TransferEngine`` under the
traffic file's management, and the dropless expert FFN. Every session is
prefilled during set-up (all experts routed) and decodes greedily through
the window; closed loop, one step in flight. A frame is one decode step of
every session: ``frame_s`` / ``wall_s`` / ``window_s`` are recorded as the
frames driver records them.

Correctness: after the window the float32 reference
(``harness/moonlight_reference.py``) runs over each session's prompt and
served tokens, layer by layer on the chip, each MoE layer's choice of
experts forced to the one the program served. Compared: the program's
logits at each prompt's last position and at every decoded position
(largest |difference| over the largest |reference logit|, per position);
the share of token-layer routings whose experts differ from the
reference's own choice; and the widest margin by which a served expert
falls below that choice, so a differing routing is a near tie. With
``ctx.control`` the float8 reference, choosing its own experts, takes the
program's place, and the check has to fail."""

from __future__ import annotations

import contextlib
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.drivers.frames import _policy
from chipbench.harness import moonlight_reference as ref
from chipbench.harness import moonlight_weights, traffic
from chipbench.harness.core import Check, Context, Outcome

REF_PAD = 512  # the reference's sequence length is a multiple of this


def model_config(c: dict):
    """The program's ModelConfig for the published DeepSeek-V3 keys."""
    from repro.models.config import ModelConfig

    want = {"hidden_act": "silu", "scoring_func": "sigmoid",
            "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
            "norm_topk_prob": True, "q_lora_rank": None, "moe_layer_freq": 1,
            "num_nextn_predict_layers": 0, "attention_bias": False}
    for k, v in want.items():
        if c.get(k) != v:
            raise ValueError(f"unsupported {k}={c.get(k)!r} (want {v!r})")
    return ModelConfig(
        name=c["name"], family="mla_moe", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], vocab=c["vocab_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        rope_theta=float(c["rope_theta"]),
        n_experts=c["n_routed_experts"], top_k=c["num_experts_per_tok"],
        n_shared_experts=c["n_shared_experts"],
        d_expert=c["moe_intermediate_size"],
        routed_scaling=c["routed_scaling_factor"],
        first_dense_layers=c["first_k_dense_replace"],
        rms_norm_eps=c["rms_norm_eps"], tie_embeddings=False,
        dtype=c["torch_dtype"])


def prompts(tr: dict, seed: int, vocab: int) -> list[np.ndarray]:
    """The sessions' prompts: sizes at evenly spaced quantiles of the mix,
    in the seed's order; token ids from the seed."""
    n = tr["sessions"]
    rng = traffic.rng_for(seed, "sessions")
    lens = traffic.sizes(tr["prompt_tokens"], n)[rng.permutation(n)]
    ids = traffic.rng_for(seed, "token_ids")
    return [ids.integers(0, vocab, int(s), dtype=np.int32) for s in lens]


class _Sessions:
    """What each session was served: its request, its slot, and per
    position its routes and logits, in the order the program made them."""

    def __init__(self, reqs):
        self.reqs = reqs
        self.slot: dict[int, int] = {}   # request index -> slot
        self.prefill_logits: dict[int, jax.Array] = {}
        self.prefill_routes: dict[int, list] = {}
        self.decode_logits: list[jax.Array] = []   # per step [B, 1, V]
        self.decode_routes: list[list] = []        # per step, per layer
        self.decode_rows: list[dict] = []          # per step: req -> row

    def take(self, eng, calls: list) -> list[int]:
        """File the calls of one engine step; returns the distinct experts
        per MoE layer of its decode."""
        pre = [c for c in calls if c.kind == "prefill"]
        dec = [c for c in calls if c.kind == "decode"]
        if pre:
            new = sorted((s, i) for i, r in enumerate(self.reqs)
                         for s, q in enumerate(eng.slots)
                         if q is r and i not in self.slot)
            if len(new) != len(pre):
                raise RuntimeError("prefills do not match admissions")
            for (s, i), c in zip(new, pre):
                self.slot[i] = s
                self.prefill_logits[i] = c.logits
                self.prefill_routes[i] = [r[0] for r in c.routes]
        if len(dec) != 1:
            raise RuntimeError(f"a step made {len(dec)} decodes")
        c = dec[0]
        self.decode_logits.append(c.logits)
        self.decode_routes.append([r[:, 0] for r in c.routes])
        self.decode_rows.append(dict(self.slot))
        return [len(np.unique(r)) for r in c.routes]


def run(ctx: Context) -> Outcome:
    from repro.core.streaming import HostStreamingExecutor
    from repro.core.transfer import TransferEngine
    from repro.models.moonlight import ExpertOffload
    from repro.serve.continuous import ContinuousBatchingEngine, Request

    c, tr = ctx.cell.config, ctx.cell.traffic
    mcfg = model_config(c)
    params = moonlight_weights.resident(c, ctx.seed)
    experts = moonlight_weights.experts(c, ctx.seed)
    max_seq = c["serve"]["max_seq"]
    te = TransferEngine(_policy(tr))
    runner = ExpertOffload(mcfg, experts, HostStreamingExecutor(te),
                           prefill_pad=c["serve"]["prefill_pad"])
    runner.calls = []
    eng = ContinuousBatchingEngine(runner.model(), params,
                                   n_slots=c["serve"]["slots"],
                                   max_seq=max_seq)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=max_seq - len(p) - 1)
            for i, p in enumerate(prompts(tr, ctx.seed, c["vocab_size"]))]
    sess = _Sessions(reqs)
    try:
        # one session a step: none waits in the engine's queue, so none is
        # shed whatever the runtime's recent deadline misses
        for i in range(tr["warmup_steps"]):
            if i < len(reqs) and not eng.submit(reqs[i]).admitted:
                raise RuntimeError(f"session {i} was not admitted")
            eng.step()
            sess.take(eng, runner.calls)
            runner.calls.clear()
        if len(sess.slot) < len(reqs):
            raise RuntimeError("warm-up left sessions unadmitted")
        ctx.setup_done()
        rec = _window(ctx, eng, runner, sess, tr)
        device = ctx.read_device()
    finally:
        eng.close()
        te.close()
    return _check(ctx, c, params, experts, sess, rec, device)


def _window(ctx, eng, runner, sess, tr) -> dict:
    wall, steps = [], []
    with contextlib.ExitStack() as stack:
        t_start = time.perf_counter()
        t_end = t_start
        while t_end - t_start < ctx.seconds:
            ctx.trace_tail(stack, t_end - t_start, tr["trace_seconds"])
            cached = [int(eng.lengths[s]) - 1
                      for s in sorted(sess.slot.values())]
            t0 = time.perf_counter()
            with ctx.span("bench.step"):
                eng.step()
            t_end = time.perf_counter()
            if eng.completed:
                raise RuntimeError("a session reached the end of its cache "
                                   "inside the window")
            experts = sess.take(eng, runner.calls)
            runner.calls.clear()
            wall.append(t_end - t0)
            steps.append((t0 - t_start, t_end - t0, cached, experts))
    return {"wall_s": wall, "frame_s": list(wall), "offload_steps": steps,
            "window_s": t_end - t_start}


# -- the check ------------------------------------------------------------------

def _layout(sess: _Sessions, vocab: int):
    """Per session: the tokens fed (prompt + served but the last), the
    positions whose logits were served, the served logits there (on the
    device) and, per MoE layer, the served routes at every fed position."""
    out = []
    for i, req in enumerate(sess.reqs):
        served = np.asarray(req.tokens, np.int64)
        if served.size and (served.min() < 0 or served.max() >= vocab):
            raise ValueError(f"session {i} served a token out of range")
        rows = [(k, d[i]) for k, d in enumerate(sess.decode_rows) if i in d]
        fed = np.concatenate([req.prompt, served[: len(rows)]])
        s = len(req.prompt)
        logits = [sess.prefill_logits[i][0, 0]] + [
            sess.decode_logits[k][row, 0] for k, row in rows]
        routes = [np.concatenate([pre] + [sess.decode_routes[k][m][row][None]
                                          for k, row in rows])
                  for m, pre in enumerate(sess.prefill_routes[i])]
        out.append({"tokens": fed, "positions": np.arange(s - 1, len(fed)),
                    "logits": logits, "routes": routes})
    return out


def _check(ctx, c, params, experts, sess, rec, device) -> Outcome:
    t_check = time.perf_counter()
    seqs = _layout(sess, c["vocab_size"])
    readings = _compare(c, params, experts, seqs, control=False)
    notes = []
    if ctx.control:
        program = readings
        readings = _compare(c, params, experts, seqs, control=True)
        notes.append(f"checked the float8 control in the program's place; "
                     f"the program reads {program!r}")
    limits = c["check"]
    checks = [Check(k, readings[k], limits[k])
              for k in ("logit_rel_err", "route_flip_share", "route_tie_gap")]
    n_cmp = sum(len(s["positions"]) for s in seqs)
    notes.append(f"compared {n_cmp} served positions of {len(seqs)} sessions "
                 f"({sum(len(s['tokens']) for s in seqs)} tokens fed) in "
                 f"{time.perf_counter() - t_check:.1f} s; "
                 f"{len(rec['wall_s'])} steps in the window")
    data = dict(rec, config=c, readings=readings)
    return Outcome(attempted=len(rec["wall_s"]), failed=0, checks=checks,
                   data=data, device=device, notes=notes)


def _compare(c: dict, params: dict, experts: list, seqs: list,
             control: bool) -> dict:
    """Run the reference over every session, layer by layer, each layer's
    experts moved to the chip once; compare with what was served (or,
    with ``control``, with the float8 control choosing its own experts)."""
    n_dense, k = c["first_k_dense_replace"], c["num_experts_per_tok"]
    pad = -(-max(len(s["tokens"]) for s in seqs) // REF_PAD) * REF_PAD
    layer = jax.jit(functools.partial(ref.layer, c), static_argnames="fp8")
    toks = [jnp.asarray(np.pad(s["tokens"], (0, pad - len(s["tokens"])))
                        [None]) for s in seqs]
    valid = [np.arange(pad) < len(s["tokens"]) for s in seqs]
    x = [ref.embed(params, t) for t in toks]        # the reference
    low = [ref.embed(params, t) for t in toks] if control else None
    flips = total = 0
    gap = 0.0
    for li, lp in enumerate(params["layers"]):
        m = li - n_dense
        ex = None if m < 0 else jax.tree.map(jnp.asarray, experts[m])
        for i, s in enumerate(seqs):
            force = None  # the experts served: the program's, or the control's
            if control:
                low[i], force, _ = layer(lp, ex, low[i], fp8=True)
            elif m >= 0:
                force = np.full((1, pad, k), np.arange(k), np.int32)
                force[0, : len(s["tokens"])] = s["routes"][m]
                force = jnp.asarray(force)
            x[i], own, g = layer(lp, ex, x[i], force)
            if m >= 0:
                differ = np.any(np.sort(np.asarray(own[0]), -1)
                                != np.sort(np.asarray(force[0]), -1), -1)
                flips += int(differ[valid[i]].sum())
                total += int(valid[i].sum())
                gap = max(gap, float(np.asarray(g[0])[valid[i]].max()))
        del ex
    err = 0.0
    head = jax.jit(functools.partial(ref.logits, c), static_argnames="fp8")
    for i, s in enumerate(seqs):
        at = jnp.asarray(s["positions"])
        want = head(params, x[i][:, at])[0]
        got = (head(params, low[i][:, at], fp8=True)[0] if control
               else jnp.stack(s["logits"]).astype(jnp.float32))
        rel = (jnp.abs(got - want).max(-1)
               / jnp.maximum(jnp.abs(want).max(-1), 1e-30))
        err = max(err, float(jnp.where(jnp.isfinite(rel), rel, jnp.inf)
                             .max()))
    return {"logit_rel_err": err, "route_flip_share": flips / max(total, 1),
            "route_tie_gap": gap}
