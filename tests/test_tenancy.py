"""The QosSpec submit-context redesign (PR 10): spec merge semantics, the
deprecation shims over the legacy ``priority=`` / ``class_caps=`` /
``rx_timeout_s=`` / ``rx_group=`` kwargs (both paths must produce
IDENTICAL arbitration), serving-layer admission control, and the
multi-tenant stress hammer with exact per-tenant byte accounting."""

import threading
import time

import numpy as np
import pytest

from repro.core.qos import (
    DEFAULT_TENANT,
    AdmissionController,
    AdmissionError,
    AdmissionPolicy,
    QosSpec,
    resolve_submit_qos,
)
from repro.core.runtime import (
    ClassQos,
    PriorityClass,
    TransferRuntime,
)
from repro.core.transfer import Ticket, TransferEngine, TransferPolicy

# ---- QosSpec semantics -----------------------------------------------------


def test_qosspec_merge_override_wins_per_field():
    base = QosSpec(priority=PriorityClass.LAYER, tenant="a", weight=2.0,
                   timeout_s=30.0)
    over = QosSpec(tenant="b", cap_bytes_per_s=1e6)
    m = base.merged(over)
    assert m.priority is PriorityClass.LAYER  # unset in override: kept
    assert m.tenant == "b"                    # set in override: wins
    assert m.weight == 2.0
    assert m.cap_bytes_per_s == 1e6
    assert m.timeout_s == 30.0
    assert base.merged(None) is base
    assert base.with_(weight=5.0).weight == 5.0


def test_qosspec_effective_tenant_defaults():
    assert QosSpec().effective_tenant == DEFAULT_TENANT
    assert QosSpec(tenant="x").effective_tenant == "x"


# ---- the deprecation shim --------------------------------------------------


def test_resolve_submit_qos_folds_legacy_priority():
    with pytest.warns(DeprecationWarning, match="priority"):
        spec = resolve_submit_qos("X.tx", None, PriorityClass.TOKEN)
    assert spec == QosSpec(priority=PriorityClass.TOKEN)
    # bare PriorityClass in the qos slot = old positional call shape
    with pytest.warns(DeprecationWarning):
        spec = resolve_submit_qos("X.tx", PriorityClass.BULK, None)
    assert spec.priority is PriorityClass.BULK
    # neither given: caller applies its default
    assert resolve_submit_qos("X.tx", None, None) is None
    with pytest.raises(TypeError):
        resolve_submit_qos("X.tx", PriorityClass.BULK, PriorityClass.TOKEN)
    with pytest.warns(DeprecationWarning):
        with pytest.raises(ValueError, match="conflicts"):
            resolve_submit_qos("X.tx", QosSpec(priority=PriorityClass.BULK),
                               PriorityClass.TOKEN)


def test_engine_submit_methods_warn_on_priority_kwarg():
    eng = TransferEngine(TransferPolicy.kernel_level())
    x = np.ones(256, np.uint8)
    with pytest.warns(DeprecationWarning, match=r"TransferEngine\.tx"):
        dev = eng.tx(x, priority=PriorityClass.BULK)
    with pytest.warns(DeprecationWarning, match=r"TransferEngine\.rx"):
        eng.rx(dev, priority=PriorityClass.BULK)
    # the replacement spelling is warning-free
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error", DeprecationWarning)
        dev = eng.tx(x, qos=QosSpec(priority=PriorityClass.BULK))
        eng.rx(dev, qos=QosSpec(priority=PriorityClass.BULK))
    eng.close()


def _arbitration_order(legacy: bool) -> list:
    """One deterministic contended workload, submitted through the legacy
    priority= kwarg or the QosSpec path; returns completion order."""
    qos = {PriorityClass.TOKEN: ClassQos(weight=8.0, deadline_s=10.0),
           PriorityClass.BULK: ClassQos(weight=1.0, deadline_s=10.0)}
    log: list = []
    with TransferRuntime(workers=1, qos=qos) as rt:
        eng = TransferEngine(TransferPolicy.kernel_level(), runtime=rt,
                             priority=PriorityClass.LAYER)
        gate = threading.Event()
        started = threading.Event()
        h = rt.register("gate", PriorityClass.LAYER)
        Ticket(*h.submit(lambda: (started.set(), gate.wait())[0]))
        assert started.wait(5.0)  # worker busy: submissions below queue
        big = np.ones(1 << 18, np.uint8)
        small = np.ones(64, np.uint8)
        tickets = []
        for i in range(4):
            if legacy:
                with pytest.warns(DeprecationWarning):
                    t = eng.tx_async(big, callback=lambda r, i=i:
                                     log.append(("bulk", i)),
                                     priority=PriorityClass.BULK)
            else:
                t = eng.tx_async(big, callback=lambda r, i=i:
                                 log.append(("bulk", i)),
                                 qos=QosSpec(priority=PriorityClass.BULK))
            tickets.append(t)
        for i in range(2):
            if legacy:
                with pytest.warns(DeprecationWarning):
                    t = eng.tx_async(small, callback=lambda r, i=i:
                                     log.append(("tok", i)),
                                     priority=PriorityClass.TOKEN)
            else:
                t = eng.tx_async(small, callback=lambda r, i=i:
                                 log.append(("tok", i)),
                                 qos=QosSpec(priority=PriorityClass.TOKEN))
            tickets.append(t)
        gate.set()
        for t in tickets:
            t.wait()
        eng.close()
    return log


def test_legacy_and_qos_paths_arbitrate_identically():
    """The shim IS the new path: the same contended workload dispatches in
    the same order whether submitted with priority= or qos=QosSpec(...)."""
    assert _arbitration_order(legacy=True) == _arbitration_order(legacy=False)


def test_serveconfig_legacy_fields_warn():
    from repro.serve.engine import ServeConfig
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error", DeprecationWarning)
        ServeConfig()  # defaults: no warning
        ServeConfig(qos=QosSpec(timeout_s=5.0, rx_group=4,
                                class_caps={"bulk": 1e9}))
    with pytest.warns(DeprecationWarning, match="class_caps"):
        ServeConfig(class_caps={"bulk": 1e9})
    with pytest.warns(DeprecationWarning, match="rx_timeout_s"):
        ServeConfig(rx_timeout_s=5.0)
    with pytest.warns(DeprecationWarning, match="rx_group"):
        ServeConfig(rx_group=1)


def test_serveconfig_legacy_fields_fold_into_engine_qos():
    from repro.serve.engine import ServeConfig, ServingEngine
    from repro.configs.registry import smoke_config
    from repro.models.api import build_model
    import jax
    cfg = smoke_config("qwen2.5-3b").replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.warns(DeprecationWarning):
        sc = ServeConfig(max_seq=64, rx_timeout_s=7.0, rx_group=2)
    legacy = ServingEngine(model, params, sc)
    assert legacy.qos.timeout_s == 7.0 and legacy.qos.rx_group == 2
    modern = ServingEngine(model, params, ServeConfig(
        max_seq=64, qos=QosSpec(timeout_s=7.0, rx_group=2)))
    assert modern.qos.timeout_s == 7.0 and modern.qos.rx_group == 2
    # identical arbitration: same decoded tokens either way
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab, (1, 8)).astype(np.int32)
    a = legacy.generate(prompts, max_new_tokens=4)[0].tokens
    b = modern.generate(prompts, max_new_tokens=4)[0].tokens
    np.testing.assert_array_equal(a, b)
    legacy.close(), modern.close()


# ---- admission control -----------------------------------------------------


def test_admission_accepts_when_idle():
    ctl = AdmissionController()  # no runtime attached
    d = ctl.decide("anyone")
    assert d.action == "accept" and d.admitted
    assert ctl.summary()["accepts"] == 1


def test_admission_queue_then_shed_on_backlog():
    """Depth ladder against a live runtime: queue at queue_depth, shed at
    shed_depth — the shed caller gets an explicit decision with a
    retry-after hint, never a hang."""
    pol = AdmissionPolicy(queue_depth=2, shed_depth=4, retry_after_s=0.01)
    with TransferRuntime(workers=1) as rt:
        ctl = AdmissionController(runtime=rt, policy=pol,
                                  cls=PriorityClass.TOKEN)
        h = rt.register("tok", PriorityClass.TOKEN)
        gate = threading.Event()
        started = threading.Event()
        Ticket(*h.submit(lambda: (started.set(), gate.wait())[0]))
        assert started.wait(5.0)
        flood = QosSpec(tenant="flood")
        tickets = [Ticket(*h.submit(lambda: None, nbytes=64, qos=flood))
                   for _ in range(4)]
        assert rt.tenant_depth(PriorityClass.TOKEN, "flood") == 4
        d = ctl.decide("flood")
        assert d.action == "shed" and not d.admitted
        assert d.retry_after_s and d.retry_after_s > 0
        assert d.queue_depth == 4
        err = AdmissionError(d)
        assert "flood" in str(err) and err.decision is d
        # a different tenant with no backlog is untouched
        assert ctl.decide("innocent").action == "accept"
        gate.set()
        for t in tickets:
            t.wait()
        # backlog drained: between queue_depth and shed_depth -> queue
        t2 = [Ticket(*h.submit(lambda: time.sleep(0.01), nbytes=64,
                               qos=flood)) for _ in range(3)]
        time.sleep(0.002)
        depth = rt.tenant_depth(PriorityClass.TOKEN, "flood")
        d2 = ctl.decide("flood")
        if 2 <= depth < 4:  # racy drain: only assert when the ladder holds
            assert d2.action == "queue" and d2.admitted
        for t in t2:
            t.wait()
        s = ctl.summary()
        assert s["sheds"] == 1
        assert "flood" in s["by_tenant"]


def test_admission_sheds_on_deadline_miss_rate():
    """The miss-rate branch: a backlogged tenant on a runtime already
    missing deadlines is shed with a window-scaled retry hint."""
    qos = {PriorityClass.TOKEN: ClassQos(weight=8.0, deadline_s=1e-4)}
    pol = AdmissionPolicy(queue_depth=64, shed_depth=256,
                          shed_miss_rate=0.5, miss_window_s=5.0)
    with TransferRuntime(workers=1, qos=qos) as rt:
        ctl = AdmissionController(runtime=rt, policy=pol,
                                  cls=PriorityClass.TOKEN)
        h = rt.register("tok", PriorityClass.TOKEN)
        gate = threading.Event()
        started = threading.Event()
        Ticket(*h.submit(lambda: (started.set(), gate.wait())[0]))
        assert started.wait(5.0)
        tickets = [Ticket(*h.submit(lambda: None, nbytes=64))
                   for _ in range(8)]
        time.sleep(0.01)  # everything queued is now past the 0.1ms deadline
        gate.set()
        for t in tickets:
            t.wait()
        assert rt.deadline_miss_rate(PriorityClass.TOKEN) >= 0.5
        # tenant with a live backlog: shed on the miss-rate branch
        gate2 = threading.Event()
        started2 = threading.Event()
        Ticket(*h.submit(lambda: (started2.set(), gate2.wait())[0]))
        assert started2.wait(5.0)
        spec = QosSpec(tenant="late")
        pending = Ticket(*h.submit(lambda: None, nbytes=64, qos=spec))
        d = ctl.decide("late")
        assert d.action == "shed" and d.miss_rate >= 0.5
        assert d.retry_after_s == pol.miss_window_s / 2
        gate2.set()
        pending.wait()


def test_continuous_batching_submit_returns_decision(own_runtime):
    from repro.configs.registry import smoke_config
    from repro.models.api import build_model
    from repro.serve.continuous import ContinuousBatchingEngine, Request
    import jax
    cfg = smoke_config("qwen2.5-3b").replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    eng = ContinuousBatchingEngine(
        model, params, n_slots=2, max_seq=64,
        admission=AdmissionPolicy(queue_depth=1, shed_depth=2))
    mk = lambda i: Request(rid=i, prompt=rng.integers(
        0, cfg.vocab, 8).astype(np.int32), max_new_tokens=3,
        qos=QosSpec(tenant="flood"))
    d0 = eng.submit(mk(0))
    assert d0.action == "accept" and d0.admitted
    d1 = eng.submit(mk(1))
    assert d1.action == "queue" and d1.admitted  # told to back off, kept
    d2 = eng.submit(mk(2))
    assert d2.action == "shed" and not d2.admitted  # NOT enqueued
    assert len(eng.queue) == 2
    done = eng.run_to_completion()
    assert sorted(r.rid for r in done) == [0, 1]  # the shed rid never ran
    s = eng.admission_summary()
    assert s["sheds"] == 1 and "flood" in s["by_tenant"]
    eng.close()


def test_continuous_batching_legacy_kwargs_warn():
    from repro.configs.registry import smoke_config
    from repro.models.api import build_model
    from repro.serve.continuous import ContinuousBatchingEngine
    import jax
    cfg = smoke_config("qwen2.5-3b").replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.warns(DeprecationWarning, match="rx_timeout_s"):
        eng = ContinuousBatchingEngine(model, params, n_slots=2,
                                       max_seq=64, rx_timeout_s=5.0)
    assert eng.qos.timeout_s == 5.0 and eng.rx_timeout_s == 5.0
    eng.close()


# ---- stress: multi-tenant hammer -------------------------------------------


@pytest.mark.stress
def test_stress_multi_tenant_hammer_exact_byte_accounting():
    """4 tenants x 2 threads hammer tx/rx roundtrips through ONE engine,
    one tenant leaf-capped: every byte lands in the right tenant row of
    the class ledger, completed == submitted per tenant, and the cap
    never starves its tenant (run under REPRO_VALIDATE_LOCKS=1 in the
    stress lane — instrumented locks assert the guarded-by discipline
    on the new tier-2 structures)."""
    rt = TransferRuntime(workers=2)
    eng = TransferEngine(TransferPolicy.kernel_level(), runtime=rt,
                         priority=PriorityClass.LAYER)
    tenants = ["t0", "t1", "t2", "t-capped"]
    rt.set_tenant_cap(PriorityClass.LAYER, "t-capped", 200e6, burst_s=0.01)
    n_threads_per, iters, n_elems = 2, 4, 8 * 1024
    per_rt = n_elems * 4 * 2  # tx + rx bytes per roundtrip
    errors: list = []

    def hammer(tenant, seed):
        try:
            spec = QosSpec(tenant=tenant)
            x = np.full(n_elems, float(seed), np.float32)
            for _ in range(iters):
                dev = eng.tx_async(x, qos=spec).wait()
                host = eng.rx_async(dev, qos=spec).wait()
                flat = np.concatenate([np.asarray(h).reshape(-1)
                                       for h in host])
                np.testing.assert_array_equal(flat, x)
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=hammer, args=(t, i))
               for i, t in enumerate(tenants)
               for _ in range(n_threads_per)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    expected = n_threads_per * iters * per_rt
    rows = rt.class_summary()["layer"]["tenants"]
    for tenant in tenants:
        row = rows[tenant]
        assert row["bytes_total"] == expected, tenant
        assert row["completed"] == row["submitted"], tenant
        assert row["cancelled"] == 0, tenant
    assert rows["t-capped"]["cap_bytes_per_s"] == 200e6
    assert rt.tenant_depth(PriorityClass.LAYER, "t0") == 0
    eng.close()
    rt.close()
