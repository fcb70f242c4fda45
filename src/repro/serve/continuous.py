"""Continuous batching: slot-based decode with per-request admission.

The batched decode step never stops for stragglers: each of the B slots
holds an independent request; finished slots are refilled by prefilling the
next queued prompt (batch=1) and splicing its KV cache into the slot. This
is the serving-side incarnation of the paper's scheduled/interrupt modes —
the engine never blocks the whole batch on one request's completion, just
as the kernel driver never blocks the PS on one DMA.

Token movement rides the same :class:`~repro.core.transfer.TransferEngine`
(or :class:`~repro.core.channels.ChannelGroup`) as the rest of the system:
prompt admission is a measured TX, each decode step's token batch is a
measured RX (issued ``rx_async`` under INTERRUPT so the device->host copy
overlaps the host-side slot bookkeeping) — the paper's balanced TX/RX goal
applied to serving, with per-transfer stats in ``engine.stats``.

Supports the KV-cache families (dense / moe / vlm) and the latent-cache
family (mla_moe, whose model drives its own programs from the host,
``Model.host_loop``: its prefill and decode are called as they are, not
jitted). The cache carries per-slot lengths [L, B] so heterogeneous
requests decode correctly in one batch (the attention layer handles vector
cache lengths); every cache leaf with a slot axis is spliced, whatever the
cache's structure.

Span: ``repro.serve.step`` around :meth:`ContinuousBatchingEngine.step`,
which starts a frame (:mod:`repro.utils.spans`) that every span inside the
step shares.
"""

from __future__ import annotations

import collections
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.qos import (
    AdmissionController,
    AdmissionDecision,
    AdmissionPolicy,
    QosSpec,
    warn_deprecated_kwarg,
)
from repro.core.runtime import PriorityClass
from repro.core.transfer import (
    Management,
    TransferEngine,
    TransferPolicy,
    reassemble_chunks,
)
from repro.models.api import Model
from repro.utils.spans import span


@dataclass
class Request:
    rid: int
    prompt: np.ndarray  # [S_prompt]
    max_new_tokens: int = 32
    tokens: list = field(default_factory=list)
    done: bool = False
    # submit context for this request's transfers (tenant, weight, caps);
    # merges over the engine's base qos. None = engine defaults.
    qos: QosSpec | None = None


def _splice_slot(batch_cache: Any, one_cache: Any, slot: int,
                 batch_dim_of) -> Any:
    """Write a batch-1 cache into slot `slot` of the batched cache."""

    def fn(dst, src):
        bd = batch_dim_of(dst)
        if bd is None:
            return dst
        if src.ndim == dst.ndim - 1:  # scalar-per-layer length -> [L, 1]
            src = src[..., None]
        start = [0] * dst.ndim
        start[bd] = slot
        return jax.lax.dynamic_update_slice(dst, src.astype(dst.dtype),
                                            tuple(start))

    return jax.tree.map(fn, batch_cache, one_cache)


class ContinuousBatchingEngine:
    """Admits requests into B decode slots; one jitted step serves all."""

    def __init__(self, model: Model, params: Any, *, n_slots: int = 4,
                 max_seq: int = 256, eos_token: int = -1,
                 transfer: "TransferEngine | Any | None" = None,
                 class_caps: "dict[str, float] | None" = None,
                 rx_timeout_s: float | None = 60.0,
                 qos: QosSpec | None = None,
                 admission: AdmissionPolicy | None = None):
        self.model = model
        self.params = params
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.eos = eos_token
        # DEPRECATED kwargs fold into the base QosSpec: class_caps ->
        # qos.class_caps, rx_timeout_s -> qos.timeout_s (the liveness
        # bound on every decoded-token RX wait; None = unbounded).
        if class_caps is not None:
            warn_deprecated_kwarg(
                "ContinuousBatchingEngine(class_caps=...)",
                "ContinuousBatchingEngine(qos=QosSpec(class_caps=...))")
        if rx_timeout_s != 60.0:
            warn_deprecated_kwarg(
                "ContinuousBatchingEngine(rx_timeout_s=...)",
                "ContinuousBatchingEngine(qos=QosSpec(timeout_s=...))")
        self.qos = QosSpec(timeout_s=rx_timeout_s,
                           class_caps=class_caps).merged(qos)
        self.rx_timeout_s = self.qos.timeout_s
        # token RXs ride TOKEN class unless the base spec overrides.
        self._tok_qos = QosSpec(priority=PriorityClass.TOKEN).merged(
            self.qos)
        # token movement (prompt TX, decoded-token RX) on a real engine —
        # callers may hand in a shared TransferEngine or ChannelGroup, which
        # close() then leaves alone (we only close what we created).
        self._owns_transfer = transfer is None
        self.transfer = transfer or TransferEngine(
            TransferPolicy.kernel_level())
        if self.qos.class_caps:
            # per-class bandwidth ceilings (PriorityClass value -> bytes/s)
            # on the runtime behind the transfer surface: bulk prefetch
            # sharing this engine's runtime can be budgeted so decode-token
            # RX keeps its headroom.
            for name, bps in self.qos.class_caps.items():
                self.transfer.set_class_cap(PriorityClass(name), bps)
        # admission valve: submit() sheds a tenant whose backlog (host
        # queue + runtime-queued descriptors) or whose class's deadline-
        # miss rate crosses the policy thresholds. Runtime read lazily —
        # engines register with the shared runtime on first submit.
        self.admission = AdmissionController(
            runtime=lambda: self.transfer.runtime,
            policy=admission, cls=PriorityClass.TOKEN)
        if model.cfg.family in ("ssm", "hybrid"):
            raise NotImplementedError(
                "continuous batching currently supports KV-cache families")
        self.queue: "collections.deque[Request]" = collections.deque()
        self.slots: list[Request | None] = [None] * n_slots
        cache = model.init_cache(n_slots, max_seq)
        # per-slot lengths: [L] -> [L, B]
        self.cache = cache._replace(
            length=jnp.zeros((model.cfg.n_layers, n_slots), jnp.int32))
        self.tokens = jnp.zeros((n_slots, 1), jnp.int32)
        self.lengths = np.zeros(n_slots, np.int64)
        # decoded-token landing zone: every step's RX writes this buffer in
        # place (rx_async out=), so steady-state decode does zero per-step
        # host allocation on the detokenize path.
        self._tok_host = np.empty(n_slots, np.int32)
        if model.host_loop:
            self._decode = model.decode
            self._prefill1 = lambda p, b: model.prefill(p, b, max_seq)
        else:
            self._decode = jax.jit(model.decode)
            self._prefill1 = jax.jit(
                lambda p, b: model.prefill(p, b, max_seq))
        self.steps = 0
        self.completed: list[Request] = []

    # -- cache plumbing ------------------------------------------------------
    def _batch_dim_of(self, leaf) -> int | None:
        if leaf.ndim >= 2 and leaf.shape[1] == self.n_slots:
            return 1  # stacked [L, B, ...]
        if leaf.ndim >= 1 and leaf.shape[0] == self.n_slots:
            return 0
        return None

    def submit(self, req: Request) -> AdmissionDecision:
        """Enqueue ``req`` unless admission sheds it. Always returns the
        explicit :class:`AdmissionDecision` — a ``shed`` decision means
        the request was NOT enqueued (check ``decision.admitted``); the
        caller backs off ``retry_after_s`` and resubmits. Never hangs,
        never silently drops."""
        spec = self.qos.merged(req.qos)
        tenant = spec.effective_tenant
        backlog = sum(
            1 for r in self.queue
            if self.qos.merged(r.qos).effective_tenant == tenant)
        decision = self.admission.decide(
            tenant, cls=self._tok_qos.priority, extra_depth=backlog)
        if decision.admitted:
            self.queue.append(req)
        return decision

    def _admit(self) -> None:
        admits: list[tuple[int, Request]] = []
        for slot in range(self.n_slots):
            if self.slots[slot] is None and self.queue:
                admits.append((slot, self.queue.popleft()))
        if not admits:
            return
        prompts = [np.ascontiguousarray(r.prompt[None], dtype=np.int32)
                   for _s, r in admits]
        specs = [self.qos.merged(r.qos) for _s, r in admits]
        # with several admissions pending, the (ragged) prompts go down as
        # ONE scatter-gather ring transaction — each prompt its own
        # descriptor segment, no per-prompt management overhead and no
        # staging copy (ragged shapes cannot share a packed payload
        # without padding anyway). One SG transaction carries ONE submit
        # context, so the batch rides SG only when every pending request
        # resolves to the same spec; mixed-tenant admissions fall back to
        # per-prompt TX to keep tenant attribution exact.
        if (len(admits) > 1 and all(s == specs[0] for s in specs)
                and self.transfer.policy.management is Management.INTERRUPT
                and hasattr(self.transfer, "tx_sg")):
            devs = self.transfer.tx_sg(prompts, qos=specs[0]).wait()
            prompt_devs = [d.reshape(p.shape)
                           for d, p in zip(devs, prompts)]
        else:
            prompt_devs = [
                reassemble_chunks(
                    self.transfer.tx(p, qos=s)).reshape(p.shape)
                for p, s in zip(prompts, specs)]
        for (slot, req), prompt_dev in zip(admits, prompt_devs):
            logits, one_cache = self._prefill1(
                self.params, {"tokens": prompt_dev})
            first = int(np.asarray(
                logits[0, -1, : self.model.cfg.vocab].argmax(-1)))
            req.tokens.append(first)
            self.cache = _splice_slot(self.cache, one_cache, slot,
                                      self._batch_dim_of)
            self.tokens = self.tokens.at[slot, 0].set(first)
            self.lengths[slot] = len(req.prompt) + 1
            self.slots[slot] = req

    def _retire(self) -> None:
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            hit_eos = self.eos >= 0 and req.tokens and req.tokens[-1] == self.eos
            if (len(req.tokens) >= req.max_new_tokens or hit_eos
                    or self.lengths[slot] >= self.max_seq - 1):
                req.done = True
                self.completed.append(req)
                self.slots[slot] = None

    def step(self) -> int:
        """Admit, decode one token for every active slot, retire. Returns
        the number of active slots served."""
        with span("repro.serve.step", new_frame=True):
            return self._step()

    def _step(self) -> int:
        self._admit()
        active = [s for s, r in enumerate(self.slots) if r is not None]
        if not active:
            return 0
        logits, self.cache = self._decode(self.params, self.tokens,
                                          self.cache)
        tok_dev = logits[:, -1, : self.model.cfg.vocab].argmax(-1)
        # next-step input stays device-resident; only the bookkeeping copy
        # crosses back to the host, as a measured RX on the engine. Under
        # INTERRUPT it rides a shared-runtime worker at TOKEN priority
        # (arbitrated ahead of bulk layer TX) while the next-step input
        # prep dispatches. With more than one active slot the per-request
        # tokens go down as ONE rx_many ring transaction — per-slot
        # tickets, one completion handoff — instead of paying the
        # per-descriptor management overhead per request (the batched-
        # submission consumer the coalescing tentpole was built for).
        interrupt = (
            self.transfer.policy.management is Management.INTERRUPT)
        if (interrupt and len(active) > 1
                and hasattr(self.transfer, "rx_many")):
            tickets = self.transfer.rx_many(
                [tok_dev[s:s + 1] for s in active],
                out=[self._tok_host[s:s + 1] for s in active],
                qos=self._tok_qos)
            self.tokens = tok_dev[:, None].astype(jnp.int32)
            for t in tickets:
                t.wait(self.rx_timeout_s)
            # per-slot landings wrote self._tok_host in place (inactive
            # slots keep stale values and are never read below).
            nxt = self._tok_host
        else:
            out = [self._tok_host]  # reused every step: zero-copy detok
            ticket = (self.transfer.rx_async([tok_dev], out=out,
                                             qos=self._tok_qos)
                      if interrupt else None)
            self.tokens = tok_dev[:, None].astype(jnp.int32)
            nxt = (ticket.wait(self.rx_timeout_s)[0] if ticket
                   else self.transfer.rx([tok_dev], out=out,
                                         qos=self._tok_qos)[0])
        nxt = np.asarray(nxt).reshape(-1)
        for slot in active:
            self.slots[slot].tokens.append(int(nxt[slot]))
            self.lengths[slot] += 1
        self.steps += 1
        self._retire()
        # the step's RX ticket is retired — a drained-ring safe point for an
        # online-adaptive transfer engine to swap plan generations (no-op
        # on plain engines/groups).
        self.transfer.maybe_adapt()
        return len(active)

    def run_to_completion(self, max_steps: int = 10_000) -> list[Request]:
        while (self.queue or any(s is not None for s in self.slots)):
            if self.steps >= max_steps:  # check BEFORE stepping: exactly
                break                    # max_steps decode steps, not +1
            if self.step() == 0 and not self.queue:
                break
        return self.completed

    def fault_summary(self) -> dict[str, Any]:
        """Deadline-miss / retry / quarantine rates of the transfer surface
        (zeroed recovery columns on a bare engine — no sibling channels)."""
        f = getattr(self.transfer, "fault_summary", None)
        if f is not None:
            return f()
        s = self.transfer.summary()
        csf = int(s.get("checksum_failures", 0))
        return {"faults": {"faults": csf, "timeouts": 0,
                           "checksum_failures": csf,
                           "retries": 0, "retry_successes": 0,
                           "quarantines": 0, "unquarantines": 0,
                           "faults_by_channel": {}},
                "quarantined": []}

    def admission_summary(self) -> dict[str, Any]:
        """Accept/queue/shed counts of the submit() valve, with per-tenant
        rows for tenants that were ever queued or shed."""
        return self.admission.summary()

    def close(self) -> None:
        if self._owns_transfer:
            self.transfer.close()
