"""Per-layer streaming executor — the NullHop execution model on a ring.

NullHop processes a multi-layer CNN *one layer at a time*: the host streams
the layer's parameters (TX), then the input feature maps; the MAC array
starts computing as soon as a couple of rows arrive; output feature maps
stream back (RX) and become the next layer's input. Total frame time is the
per-layer sum of (TX + compute + RX), with overlap determined by the
transfer policy.

Here the same execution model serves models whose parameters exceed device
memory (or that we deliberately execute layer-resident to minimise HBM
footprint). Under an INTERRUPT policy with ring depth >= 2 the executor runs
**three-way overlap** — the paper's balanced-TX/RX goal:

    TX(layer k+1)  ─┐
    compute(k)      ├─ concurrent (per-engine completion workers + main thread)
    RX(layer k-1)  ─┘

Layer k+1's parameters are packed into their cached :class:`StagedLayout`
staging buffer and stream host->device while layer k computes; layer k-1's
output feature map streams device->host (``rx_async``) at the same time.
Staging layouts are resolved once per layer identity through the engine's
:class:`LayoutCache`, so steady-state frames do zero pack allocation — and
zero pack *copies* when the host params are unchanged (inference weight
streaming), the ZynqNet one-time-layout lesson.

The seed's per-frame pack path (a fresh staging buffer per layer per frame,
depth-2 max) is kept behind ``staged=False`` as the benchmark baseline.

Two implementations:

- :class:`HostStreamingExecutor` — real host->device staging; used by
  :mod:`repro.accel.nullhop`, the streaming benchmarks and, for a MoE
  model's routed experts kept in host memory, :mod:`repro.models.moonlight`
  (the other serving models keep every weight on the device).
- :func:`device_streamed_scan` — the on-device analogue for the dry-run: a
  ``jax.lax.scan`` over layers where each layer's params are all-gathered
  from their sharded resting place just-in-time (the TPU equivalent of
  per-layer TX), letting XLA overlap the gather of layer k+1 with layer k's
  compute. This is what the multi-pod configs lower.
"""

from __future__ import annotations

import collections
from dataclasses import dataclass, field
from typing import Any, Callable, Sequence

import jax
import numpy as np

from repro.core.transfer import (
    Management,
    StagedLayout,
    Ticket,
    TransferEngine,
    reassemble_chunks,
)
from repro.utils.spans import span


@dataclass
class LayerTiming:
    name: str
    tx_s: float
    compute_s: float
    rx_s: float
    tx_bytes: int
    rx_bytes: int

    @property
    def total_s(self) -> float:
        return self.tx_s + self.compute_s + self.rx_s


@dataclass
class FrameTiming:
    """Timing of one full multi-layer execution (one 'frame' in the paper)."""

    layers: list[LayerTiming] = field(default_factory=list)

    @property
    def frame_s(self) -> float:
        return sum(l.total_s for l in self.layers)

    @property
    def tx_us_per_byte(self) -> float:
        b = sum(l.tx_bytes for l in self.layers)
        t = sum(l.tx_s for l in self.layers)
        return t * 1e6 / max(b, 1)

    @property
    def rx_us_per_byte(self) -> float:
        b = sum(l.rx_bytes for l in self.layers)
        t = sum(l.rx_s for l in self.layers)
        return t * 1e6 / max(b, 1)


class HostStreamingExecutor:
    """Run a sequence of layers, staging each layer's params host->device
    under the engine's policy, with ring-depth-controlled prefetch.

    ``layers`` is a list of (name, param_host_arrays, apply_fn) where
    ``apply_fn(params_device_list, x)`` returns the layer output. With an
    INTERRUPT policy of ring depth >= 2 the executor overlaps layer k+1's TX
    *and* layer k-1's RX with layer k's compute; with POLLING everything
    serialises.

    ``engine`` may be a single :class:`TransferEngine` or a
    :class:`repro.core.channels.ChannelGroup` — the group stripes each
    layer's payload across its member rings (multi-channel DMA), and the
    executor code is identical because the group duck-types the engine.

    ``staged=False`` selects the legacy per-frame pack path (re-concatenates
    params every frame) — kept only as the measured baseline for
    ``BENCH_transfer.json``.

    Spans (:mod:`repro.utils.spans`): ``repro.stream.input_tx``, and per
    layer ``repro.stream.tx_wait`` (the wait for the layer's parameters —
    the whole transfer where it is synchronous — and their unpack),
    ``repro.stream.pack`` (staging pack and submit, ``nbytes`` = layout
    bytes), ``repro.stream.compute`` (the layer program to completion) and
    ``repro.stream.rx_wait``. :class:`LayerTiming` is computed from their
    stamps: ``tx_s`` is the wait plus the packs issued right after it, and
    layer 0's also holds the input TX.

    ``sensor_fn``: optional frame-ingest callable, registered as a
    ``SENSOR``-class background task for the duration of each ``run()`` —
    the paper's concurrent collection+transfer scenario. Under INTERRUPT
    management the shared runtime gives it budgeted slices between
    completion dispatches; under SCHEDULED the cooperative scheduler
    interleaves it between DMA chunks; under POLLING it starves (the
    paper's warning: the polling driver blocks the whole system).

    :attr:`last_outputs` keeps the last ``run()``'s per-layer host fmaps;
    :mod:`repro.accel.nullhop` reads its per-layer sparsity from them.

    :meth:`stream_routed` streams parameter groups that are chosen only
    once a layer has started (a MoE layer's routed experts, after its
    router ran) and hands each to the caller as it lands.
    """

    def __init__(self, engine: "TransferEngine | Any", *, staged: bool = True,
                 zero_copy_rx: bool = True,
                 sensor_fn: Callable[[], None] | None = None):
        self.engine = engine
        self.staged = staged
        self.sensor_fn = sensor_fn
        self.sensor_slices = 0  # background slices observed across runs
        # per-layer host output buffers, reused frame after frame: with
        # ``zero_copy_rx`` each INTERIOR layer's fmap RX lands in the SAME
        # executor-owned buffer every frame (``rx_async(..., out=)``), so
        # steady-state frames allocate nothing on the readback side. The
        # FINAL layer's output — the frame result handed to the caller —
        # is always a fresh array, so callers may keep frames without them
        # aliasing each other.
        self.zero_copy_rx = zero_copy_rx
        self._rx_bufs: dict[Any, np.ndarray] = {}
        self._outputs: list[np.ndarray] = []

    @property
    def last_outputs(self) -> tuple[np.ndarray, ...]:
        """The last ``run()``'s host fmaps, one per layer, in layer order:
        references to what RX delivered, not copies. Interior entries may
        be the reused ``zero_copy_rx`` buffers, so they are valid until the
        next ``run()`` of this executor; the last entry is the array that
        ``run()`` returned."""
        return tuple(self._outputs)

    @property
    def window(self) -> int:
        """Groups :meth:`stream_routed` keeps in flight: ``depth - 1`` of
        the engine's policy, at least one."""
        return max(1, self.engine.policy.depth - 1)

    def _rx_out(self, key: Any, y: jax.Array, *,
                last: bool) -> list[np.ndarray] | None:
        if not self.zero_copy_rx or last:
            return None
        shape, dtype = tuple(y.shape), np.dtype(y.dtype)
        buf = self._rx_bufs.get(key)
        if buf is None or buf.shape != shape or buf.dtype != dtype:
            buf = np.empty(shape, dtype)
            self._rx_bufs[key] = buf
        return [buf]

    def _frame_end(self) -> None:
        """End-of-frame safe point: the ring is drained (every ticket
        retired), so an adaptive engine may swap its plan generation now
        (no-op on plain engines/groups)."""
        self.engine.maybe_adapt()

    def _register_sensor(self) -> Callable[[], None]:
        """Register ``sensor_fn`` as a SENSOR-class background task on the
        engine's completion backend; returns the unregister callable.
        Both registrars (runtime, cooperative scheduler) share the
        register -> unregister-callable contract, so one wrapper serves
        both. POLLING has no backend: the host is blocked for the
        duration of every transfer — collection starves, which IS the
        paper's result."""
        if self.sensor_fn is None:
            return lambda: None
        mgmt = self.engine.policy.management
        registrar = None
        if mgmt is Management.INTERRUPT:
            registrar = getattr(self.engine, "runtime", None)
        elif mgmt is Management.SCHEDULED:
            registrar = getattr(self.engine, "_scheduler", None)
        if registrar is None:
            return lambda: None
        count = {"n": 0}

        def counted() -> None:
            count["n"] += 1
            self.sensor_fn()

        inner = registrar.register_background(counted)

        def unregister() -> None:
            inner()
            self.sensor_slices += count["n"]
        return unregister

    def run(
        self,
        layers: Sequence[tuple[str, list[np.ndarray], Callable[..., jax.Array]]],
        x: np.ndarray,
    ) -> tuple[np.ndarray, FrameTiming]:
        policy = self.engine.policy
        overlapped = (
            policy.management is Management.INTERRUPT and policy.depth >= 2
        )
        self._outputs = []
        unregister_sensor = self._register_sensor()
        try:
            if overlapped and self.staged:
                out = self._run_overlapped(layers, x)
            else:
                out = self._run_basic(layers, x, prefetch=overlapped)
        finally:
            unregister_sensor()
        self._frame_end()
        return out

    # -- groups chosen at run time (routed experts) ------------------------
    def stream_routed(
        self,
        groups: Sequence[Sequence[np.ndarray]],
        apply_fn: Callable[[int, list[jax.Array]], None],
    ) -> None:
        """Move each group of host arrays to the device and call
        ``apply_fn(j, device_arrays)`` for group j, in order, as it lands.

        A group goes as one ``tx_sg`` transaction, one segment per array,
        straight from the host arrays (no staging copy). Up to
        :attr:`window` groups are in flight while the host hands landed ones
        to ``apply_fn``, which dispatches device work without waiting for
        it: group j+1's TX overlaps group j's compute. INTERRUPT management
        only.

        Spans: ``repro.stream.expert_tx`` per group's submit (``nbytes`` =
        the group's bytes; its transfer's ``repro.xfer.tx`` record is its
        child) and ``repro.stream.expert_wait`` per group, the wait for it
        to land."""
        engine = self.engine
        if engine.policy.management is not Management.INTERRUPT:
            raise ValueError("stream_routed needs INTERRUPT management")
        sizes = [sum(int(a.nbytes) for a in g) for g in groups]
        window = self.window
        pending: collections.deque = collections.deque()
        issued = 0

        def issue() -> None:
            nonlocal issued
            while issued < len(groups) and len(pending) < window:
                with span("repro.stream.expert_tx", sizes[issued]):
                    pending.append(engine.tx_sg(list(groups[issued])))
                issued += 1

        issue()
        for j in range(len(groups)):
            with span("repro.stream.expert_wait"):
                dev = pending.popleft().wait()
            issue()
            apply_fn(j, dev)

    # -- shared input staging ----------------------------------------------
    def _tx_input(self, x: np.ndarray) -> tuple[jax.Array, float, int]:
        xa = np.asarray(x)
        with span("repro.stream.input_tx", xa.nbytes) as s:
            dev_chunks = self.engine.tx(xa)
            x_dev = reassemble_chunks(dev_chunks).reshape(xa.shape)
        return x_dev, s.ns * 1e-9, xa.nbytes

    # -- new path: cached layouts + three-way overlap -----------------------
    def _run_overlapped(self, layers, x) -> tuple[np.ndarray, FrameTiming]:
        engine = self.engine
        policy = engine.policy
        timing = FrameTiming()
        x_dev, input_tx_s, input_bytes = self._tx_input(x)
        if not layers:
            # no layers: the frame is the transferred input itself, not None
            host_out = engine.rx([x_dev])[0]
            return host_out, timing

        layouts: list[StagedLayout] = [
            engine.layouts.get((i, name), params)
            for i, (name, params, _) in enumerate(layers)
        ]

        # TX window: keep up to depth-1 layer streams in flight ahead of the
        # layer being computed (the descriptor-ring in-flight rule; slot
        # `depth` is reserved for the concurrent RX stream).
        tx_window = max(1, policy.depth - 1)
        pending_tx: list[tuple[str, Ticket]] = []  # ("pack"|"sg", ticket)
        next_tx = 0
        # per-layer-set pack-vs-SG gate: few large params ride scatter-gather
        # segments (one ring slot, zero staging memcpy); many small params
        # keep the staged pack. Decisions are memoized per layer key in the
        # LayoutCache and re-priced when the online fit moves the crossover.
        sg_capable = (hasattr(engine, "tx_sg")
                      and hasattr(engine, "prefer_sg")
                      and policy.management is Management.INTERRUPT)

        def issue_tx() -> int:
            """Refill the TX window; returns the nanoseconds its packs and
            submits took."""
            nonlocal next_tx
            pack_ns = 0
            while next_tx < len(layers) and len(pending_tx) < tx_window:
                name, params, _ = layers[next_tx]
                lay = layouts[next_tx]
                with span("repro.stream.pack", lay.nbytes) as s:
                    if sg_capable and engine.layouts.decide_sg(
                            (next_tx, name), lay, engine.prefer_sg):
                        pending_tx.append(
                            ("sg", engine.tx_sg(lay.sg_segments(params))))
                    else:
                        payload = lay.pack(params)
                        pending_tx.append(
                            ("pack", engine.tx_async(payload, layout=lay)))
                pack_ns += s.ns
                next_tx += 1
            return pack_ns

        issue_tx()

        pending_rx: tuple[int, Ticket] | None = None  # (layer idx, ticket)
        host_out: np.ndarray | None = None

        def drain_rx() -> None:
            nonlocal pending_rx, host_out
            if pending_rx is None:
                return
            j, ticket = pending_rx
            with span("repro.stream.rx_wait") as s:
                host_out = ticket.wait()[0]
            self._outputs.append(host_out)
            timing.layers[j].rx_s += s.ns * 1e-9
            pending_rx = None

        for i, (name, params_host, apply_fn) in enumerate(layers):
            # --- TX: wait for this layer's in-flight params, then refill the
            # ring window (layers i+1 .. i+depth-1 stream during compute);
            # the refill's packs count as this layer's TX time
            with span("repro.stream.tx_wait") as s:
                kind, ticket = pending_tx.pop(0)
                if kind == "sg":
                    # SG segments are whole arrays: results arrive shaped,
                    # no staging unpack (and no staging buffer was touched).
                    params_dev = ticket.wait()
                else:
                    params_dev = layouts[i].unpack(ticket.wait())
            tx_s = (s.ns + issue_tx()) * 1e-9
            tx_bytes = layouts[i].nbytes
            if i == 0:
                tx_s += input_tx_s
                tx_bytes += input_bytes

            # --- compute (layer k-1's RX and layer k+1's TX are in flight)
            with span("repro.stream.compute") as s:
                y = apply_fn(params_dev, x_dev)
                y.block_until_ready()

            rx_bytes = int(y.size) * y.dtype.itemsize
            timing.layers.append(
                LayerTiming(name, tx_s, s.ns * 1e-9, 0.0, tx_bytes, rx_bytes)
            )
            # --- RX: retire layer k-1's ticket, launch layer k's — an
            # interior fmap streams back into its reused host buffer; the
            # final layer's (the caller's frame result) gets a fresh one
            drain_rx()
            pending_rx = (i, engine.rx_async(
                [y], out=self._rx_out(i, y, last=i == len(layers) - 1)))
            x_dev = y  # next layer consumes device-resident output
        drain_rx()
        return host_out, timing

    # -- legacy/basic path: per-frame pack, serial (or depth-2 TX prefetch) --
    def _run_basic(self, layers, x, *, prefetch: bool) -> tuple[np.ndarray, FrameTiming]:
        timing = FrameTiming()
        x_dev, input_tx_s, input_bytes = self._tx_input(x)
        if not layers:
            host_out = self.engine.rx([x_dev])[0]
            return host_out, timing

        def tx_layer(params: list[np.ndarray]
                     ) -> tuple[StagedLayout, Ticket, int]:
            # seed path: a fresh staging layout (allocation + copy) per frame
            with span("repro.stream.pack") as s:
                lay = StagedLayout(params)
                s.nbytes = lay.nbytes
                ticket = self.engine.tx_async(lay.pack(params), layout=lay)
            return lay, ticket, s.ns

        pending: tuple[StagedLayout, Ticket, int] | None = None
        if prefetch and layers:
            pending = tx_layer(layers[0][1])

        host_out: np.ndarray | None = None
        for i, (name, params_host, apply_fn) in enumerate(layers):
            # --- TX params for this layer (with prefetch, the next layer's
            # pack and submit count as this layer's TX time)
            if prefetch:
                lay, ticket, _ = pending
                with span("repro.stream.tx_wait") as s:
                    params_dev = lay.unpack(ticket.wait())
                tx_ns = s.ns
                # issue next layer's TX immediately (overlaps compute below)
                if i + 1 < len(layers):
                    pending = tx_layer(layers[i + 1][1])
                    tx_ns += pending[2]
            else:
                with span("repro.stream.pack") as pk:
                    lay = StagedLayout(params_host)
                    pk.nbytes = lay.nbytes
                    payload = lay.pack(params_host)
                with span("repro.stream.tx_wait") as s:
                    params_dev = lay.unpack(self.engine.tx(payload))
                tx_ns = pk.ns + s.ns
            tx_s = tx_ns * 1e-9
            tx_bytes = sum(np.asarray(p).nbytes for p in params_host)
            if i == 0:
                tx_s += input_tx_s
                tx_bytes += input_bytes

            # --- compute
            with span("repro.stream.compute") as s:
                y = apply_fn(params_dev, x_dev)
                y.block_until_ready()
            compute_s = s.ns * 1e-9

            # --- RX (per the paper, each layer's output returns to the PS)
            with span("repro.stream.rx_wait") as s:
                host_out = self.engine.rx(
                    [y], out=self._rx_out(i, y, last=i == len(layers) - 1))[0]
            self._outputs.append(host_out)

            timing.layers.append(LayerTiming(
                name, tx_s, compute_s, s.ns * 1e-9, tx_bytes, host_out.nbytes))
            x_dev = y  # next layer consumes device-resident output
        return host_out, timing


def device_streamed_scan(
    layer_fn: Callable[[Any, jax.Array], jax.Array],
    stacked_params: Any,
    x: jax.Array,
    *,
    gather_fn: Callable[[Any], Any] | None = None,
    unroll: int = 1,
) -> jax.Array:
    """On-device per-layer streaming: scan over stacked layer params.

    ``gather_fn`` (if given) materialises one layer's params from their
    sharded/compressed resting state — the device-side analogue of the
    per-layer TX. XLA schedules the gather of iteration k+1 concurrently
    with iteration k's compute when the dependency allows (double buffer)."""

    def body(carry, layer_params):
        if gather_fn is not None:
            layer_params = gather_fn(layer_params)
        return layer_fn(layer_params, carry), None

    y, _ = jax.lax.scan(body, x, stacked_params, unroll=unroll)
    return y
