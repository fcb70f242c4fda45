"""Sensor frames through the program's layer-streamed CNN executor.

The timed path is ``NullHopExecutor.run_frame``: per conv layer the
weights and feature maps stream under the traffic file's transfer
management, the layer runs on the chip, and the FC head runs on the host.
Closed loop, one frame in flight. Every frame's logits are compared with
the plain reference after the window."""

from __future__ import annotations

import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.harness import reference, traffic
from chipbench.harness.core import Check, Context, Outcome
from chipbench.harness.weights import cnn_weights


def _policy(tr: dict):
    from repro.core.transfer import TransferPolicy

    mgmt = tr["management"]
    if mgmt == "kernel_level_ring":
        return TransferPolicy.kernel_level_ring(tr["ring_depth"])
    if mgmt in ("user_level_polling", "user_level_scheduled", "kernel_level"):
        return getattr(TransferPolicy, mgmt)()
    raise ValueError(f"unknown management {mgmt!r}")


def _cnn(cfg: dict):
    from repro.accel.roshambo import ConvSpec, RoShamBoCNN, RoShamBoConfig

    specs, c_in = [], cfg["input_channels"]
    for s in cfg["layers"]:
        specs.append(ConvSpec(s["name"], c_in, s["c_out"], s["kernel"],
                              s["pool"]))
        c_in = s["c_out"]
    return RoShamBoCNN(RoShamBoConfig(
        input_hw=cfg["input_hw"], n_classes=cfg["n_classes"],
        layers=tuple(specs), dtype=cfg["dtype"]))


def run(ctx: Context) -> Outcome:
    from repro.accel.nullhop import NullHopExecutor

    cfg, tr = ctx.cell.config, ctx.cell.traffic
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        # weights live on the host and stream to the chip layer by layer
        params = jax.tree.map(np.asarray, cnn_weights(cfg, ctx.seed))
        pool = traffic.dvs_frames(cfg["frames"], cfg["input_hw"],
                                  cfg["input_channels"], ctx.seed)
        order = traffic.frame_order(len(pool), ctx.seed)
        ex = NullHopExecutor(_cnn(cfg), _policy(tr))
        try:
            for i in range(tr["warmup_frames"]):
                ex.run_frame(params, pool[order[i % len(pool)]])
            ctx.setup_done()
            rec = _window(ctx, ex, params, pool, order, tr)
            rt = ex.engine.runtime
            rec["runtime_classes"] = rt.class_summary() if rt else None
            device = ctx.read_device()
        finally:
            ex.close()
    return _check(ctx, cfg, params, pool, rec, device)


def _window(ctx, ex, params, pool, order, tr) -> dict:
    idx, wall, frame_s, tx, rx, logits = [], [], [], [], [], []
    with contextlib.ExitStack() as stack:
        t_start = time.perf_counter()
        t_end = t_start
        i = 0
        while t_end - t_start < ctx.seconds:
            ctx.trace_tail(stack, t_end - t_start, tr["trace_seconds"])
            fi = int(order[i % len(pool)])
            t0 = time.perf_counter()
            with ctx.span("bench.run_frame"):
                res = ex.run_frame(params, pool[fi])
            t_end = time.perf_counter()
            idx.append(fi)
            wall.append(t_end - t0)
            frame_s.append(res.timing.frame_s)
            tx.append(sum(l.tx_s for l in res.timing.layers))
            rx.append(sum(l.rx_s for l in res.timing.layers))
            logits.append(np.array(res.logits, np.float32))
            i += 1
    return {"frame_index": idx, "wall_s": wall, "frame_s": frame_s,
            "tx_wait_s": tx, "rx_wait_s": rx, "logits": logits,
            "window_s": t_end - t_start}


def _check(ctx, cfg, params, pool, rec, device) -> Outcome:
    """Every frame's logits against the reference on its frame, as the
    largest error relative to the frame's largest reference logit. With
    ``ctx.control`` the control's logits (three bf16 passes) take the
    program's place, and the check has to fail."""
    x = jnp.asarray(pool[:, 0])
    ref_fn = jax.jit(lambda p, x: reference.cnn_logits(cfg, p, x))
    ref = np.asarray(ref_fn(params, x), np.float64)
    good = [(fi, got) for fi, got in zip(rec["frame_index"], rec["logits"])
            if got.shape == (1, ref.shape[1]) and np.all(np.isfinite(got))]
    failed = len(rec["frame_index"]) - len(good)
    idx = [fi for fi, _g in good]
    readings = {"program": reference.rel_err(
        np.concatenate([g for _f, g in good]), ref[idx]) if good else 0.0}
    notes = []
    if ctx.control:
        low_fn = jax.jit(lambda p, x: reference.cnn_logits(cfg, p, x,
                                                           control=True))
        low = np.asarray(low_fn(params, x), np.float64)
        readings["control"] = reference.rel_err(low[idx], ref[idx]) \
            if good else 0.0
        notes.append(f"checked the three-pass control in the program's "
                     f"place; the program reads {readings['program']!r}")
    who = "control" if ctx.control else "program"
    checks = [Check("logit_rel_err", readings[who],
                    cfg["check"]["logit_rel_err"])]
    data = dict(rec, config=cfg, readings=readings)
    return Outcome(attempted=len(rec["frame_index"]), failed=failed,
                   checks=checks, data=data, device=device, notes=notes)
