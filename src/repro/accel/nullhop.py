"""NullHop-style accelerator executor: per-layer streamed CNN execution.

Reproduces the paper's scenario 2 (Table I): each layer of the CNN is
executed as TX(params + input fmap) -> compute -> RX(output fmap), with the
transfer policy deciding how the TX/RX DMAs are managed. Built on
:class:`repro.core.streaming.HostStreamingExecutor`, so the three driver
modes and the buffering/partitioning knobs all apply.

Also models NullHop's sparsity awareness: the accelerator skips zero
activations (sparse feature-map encoding); we report the measured activation
sparsity per layer (ReLU output) alongside timings, since it determines the
effective RX payload on the real device. It is counted on the host over the
output fmaps the stream already returned (the per-layer RX), so it costs no
second pass over the network and no device work.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np

from repro.accel.roshambo import RoShamBoCNN
from repro.core.streaming import FrameTiming, HostStreamingExecutor
from repro.core.transfer import TransferEngine, TransferPolicy
from repro.utils.spans import span


@dataclass
class NullHopResult:
    logits: np.ndarray
    timing: FrameTiming
    sparsity: list[float]  # per-layer zero fraction of the output fmap
    policy_tag: str


class NullHopExecutor:
    """Executes a RoShamBoCNN per-layer under a transfer policy.

    ``staged=True`` (default) streams through the engine's cached
    :class:`~repro.core.transfer.StagedLayout` ring path — layer weights are
    laid out once and re-staged copy-free on every subsequent frame;
    ``staged=False`` keeps the seed per-frame pack path for comparison."""

    def __init__(self, cnn: RoShamBoCNN, policy: TransferPolicy, *,
                 staged: bool = True):
        self.cnn = cnn
        self.policy = policy
        self.staged = staged
        self.engine = TransferEngine(policy)
        # one compiled step per layer, kept across frames
        self._steps: dict = {}

    def close(self) -> None:
        self.engine.close()

    def run_frame(self, params: dict, frame: np.ndarray) -> NullHopResult:
        """frame: [B, H, W, C]. Per-layer streamed execution + final FC.

        Spans: ``repro.nullhop.frame`` (the whole call; it starts the
        frame every nested and worker-side span shares), and inside it
        ``repro.nullhop.stream`` (the streamed layers),
        ``repro.nullhop.oracle`` (the sparsity count, one
        ``repro.nullhop.oracle.layer`` per layer, ``nbytes`` = the fmap it
        counted) and ``repro.nullhop.fc`` (the host head).

        ``sparsity`` is each layer's zero fraction, counted on the host
        over the output fmap its RX returned
        (:attr:`HostStreamingExecutor.last_outputs`): no jax op, transfer
        or sync. NaN counts as non-zero, as in ``(y == 0).mean()``."""
        with span("repro.nullhop.frame", new_frame=True):
            return self._run_frame(params, frame)

    def _run_frame(self, params: dict, frame: np.ndarray) -> NullHopResult:
        cnn = self.cnn

        def make_apply(spec):
            def apply_fn(dev_params, x):
                w, b = dev_params
                return cnn.layer_apply(spec, {"w": w, "b": b}, x)
            if spec.name not in self._steps:
                self._steps[spec.name] = jax.jit(apply_fn)
            return self._steps[spec.name]

        layers = []
        for spec in cnn.cfg.layers:
            p = params[spec.name]
            layers.append((spec.name, [np.asarray(p["w"]), np.asarray(p["b"])],
                           make_apply(spec)))

        executor = HostStreamingExecutor(self.engine, staged=self.staged)
        with span("repro.nullhop.stream"):
            out_host, timing = executor.run(layers, np.asarray(frame))

        sparsity = []
        with span("repro.nullhop.oracle"):
            for y in executor.last_outputs:
                with span("repro.nullhop.oracle.layer", y.nbytes):
                    sparsity.append(1.0 - np.count_nonzero(y) / y.size)

        # classifier head runs on the PS in the paper (host-side)
        with span("repro.nullhop.fc"):
            feats = out_host.reshape(out_host.shape[0], -1)
            logits = (feats @ np.asarray(params["fc"]["w"])
                      + np.asarray(params["fc"]["b"]))
        return NullHopResult(logits, timing, sparsity, self.policy.tag)
