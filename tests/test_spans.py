"""The in-memory span recorder: nesting, frames carried to worker-side
records, the bounded ring, the off switch, and the frame path's spans
against the LayerTiming and byte counts they are the source of."""

import threading

import jax
import numpy as np
import pytest

from repro.accel.nullhop import NullHopExecutor
from repro.accel.roshambo import RoShamBoCNN, RoShamBoConfig
from repro.core.transfer import TransferEngine, TransferPolicy
from repro.utils import spans


@pytest.fixture(autouse=True)
def _fresh_recorder():
    spans.clear()
    yield
    spans.set_enabled(True)
    spans.clear()


def _by_name(records, name):
    return [r for r in records if r.name == name]


def test_nesting_gives_parents_frames_and_durations():
    with spans.span("repro.t.root", new_frame=True) as root:
        with spans.span("repro.t.child", nbytes=12) as child:
            with spans.span("repro.t.leaf"):
                assert spans.current() == (root._seq, root._seq + 2)
        with spans.span("repro.t.sibling"):
            pass
    assert spans.current() == (None, None)
    with spans.span("repro.t.outside"):
        pass
    recs = {r.name: r for r in spans.snapshot()}
    r, c = recs["repro.t.root"], recs["repro.t.child"]
    assert (r.parent, r.frame) == (None, r.seq)
    assert (c.parent, c.frame, c.nbytes) == (r.seq, r.seq, 12)
    assert recs["repro.t.leaf"].parent == c.seq
    assert recs["repro.t.sibling"].parent == r.seq
    assert recs["repro.t.outside"].frame is None
    assert r.t0 <= c.t0 <= c.t1 <= r.t1
    assert (root.ns, child.ns) == (r.t1 - r.t0, c.t1 - c.t0)
    assert {x.thread for x in recs.values()} == {
        threading.current_thread().name}


def test_span_lands_in_the_profilers_trace(tmp_path):
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("repro.t.profiled"):
            pass
    finally:
        jax.profiler.stop_trace()
    with spans.span("repro.t.after"):
        pass
    (path,) = tmp_path.glob("**/*.xplane.pb")
    names = {e.name for p in ProfileData.from_file(str(path)).planes
             for line in p.lines for e in line.events}
    assert "repro.t.profiled" in names and "repro.t.after" not in names
    assert [r.name for r in spans.snapshot()] == ["repro.t.profiled",
                                                  "repro.t.after"]


def test_worker_records_carry_the_submitters_frame(own_runtime):
    eng = TransferEngine(TransferPolicy.kernel_level_ring(4),
                         runtime=own_runtime)
    try:
        with spans.span("repro.t.frame", new_frame=True) as root:
            with spans.span("repro.t.submit") as sub:
                dev = eng.tx_async(np.arange(4096, dtype=np.float32)).wait()
            eng.rx_async(dev).wait()
    finally:
        eng.close()
    recs = [r for r in spans.snapshot() if r.frame == root._seq]
    main = threading.current_thread().name
    names = {r.name for r in recs}
    assert {"repro.xfer.tx", "repro.xfer.rx", "repro.runtime.queue.layer",
            "repro.runtime.service.layer"} <= names
    workers = [r for r in recs if r.name.startswith(("repro.xfer.",
                                                     "repro.runtime."))]
    assert workers and all(r.thread != main for r in workers)
    tx = _by_name(recs, "repro.xfer.tx")
    assert [(r.parent, r.nbytes) for r in tx] == [(sub._seq, 4096 * 4)]
    assert [r.parent for r in _by_name(recs, "repro.xfer.rx")] == [root._seq]
    queued = [r for r in recs if r.name.startswith("repro.runtime.queue.")]
    assert {r.parent for r in queued} == {sub._seq, root._seq}
    assert all(r.t0 <= r.t1 for r in recs)


def test_ring_drops_its_oldest_records():
    n = spans.CAPACITY + 10
    for i in range(n):
        spans.record("repro.t.r", i, i + 1, parent=None, frame=None)
    recs = spans.snapshot()
    assert len(recs) == spans.CAPACITY
    assert (recs[0].t0, recs[-1].t0) == (10, n - 1)


def test_thread_churn_keeps_the_recorders_tables_bounded():
    # workers that idle out and start again must not grow the recorder
    names0 = len(spans._names)

    def work(i):
        with spans.span("repro.t.churn", new_frame=True):
            frame, parent = spans.current()
            spans.record("repro.t.worker", i, i + 1, parent=parent,
                         frame=frame)

    n = 3 * spans.THREAD_NAMES
    for i in range(n):
        t = threading.Thread(target=work, args=(i,))
        t.start()
        t.join()
    assert len(spans._names) <= names0 + 2
    assert len(spans._threads) <= spans.THREAD_NAMES + 1
    recs = _by_name(spans.snapshot(), "repro.t.worker")
    assert len(recs) == n and len({r.frame for r in recs}) == n
    main = threading.current_thread()
    with spans.span("repro.t.main"):
        pass
    assert _by_name(spans.snapshot(), "repro.t.main")[0].thread == main.name


def test_recorder_imports_no_jax():
    import subprocess
    import sys

    code = ("import importlib.util, sys\n"
            "spec = importlib.util.spec_from_file_location('spans', %r)\n"
            "m = importlib.util.module_from_spec(spec)\n"
            "spec.loader.exec_module(m)\n"
            "with m.span('repro.t.x', new_frame=True):\n"
            "    pass\n"
            "assert [r.name for r in m.snapshot()] == ['repro.t.x']\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n"
            % spans.__file__)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60)
    assert out.returncode == 0, out.stderr


def test_counters_file_under_the_open_frame_and_clear():
    with spans.span("repro.t.root", new_frame=True) as root:
        spans.count("repro.t.moved", 21)
        with spans.span("repro.t.child"):
            spans.count("repro.t.moved", 3)
    spans.count("repro.t.moved", 5)
    got = [(c.name, c.value, c.frame) for c in spans.counts()]
    assert got == [("repro.t.moved", 21, root._seq),
                   ("repro.t.moved", 3, root._seq),
                   ("repro.t.moved", 5, None)]
    ts = [c.t for c in spans.counts()]
    assert ts == sorted(ts)
    spans.set_enabled(False)
    spans.count("repro.t.moved", 7)
    assert len(spans.counts()) == 3
    spans.clear()
    assert spans.counts() == []


def test_disabled_recorder_records_nothing_but_still_times():
    spans.set_enabled(False)
    with spans.span("repro.t.off", new_frame=True) as s:
        assert spans.current() == (None, None)
    spans.record("repro.t.off", 0, 1, parent=None, frame=None)
    assert spans.snapshot() == []
    assert s.ns >= 0


@pytest.mark.parametrize("policy", [TransferPolicy.kernel_level_ring(4),
                                    TransferPolicy.user_level_polling()],
                         ids=lambda p: p.tag)
def test_frame_spans_are_the_layer_timing(policy):
    cnn = RoShamBoCNN(RoShamBoConfig(input_hw=16))
    params = jax.tree.map(np.asarray, cnn.init(jax.random.PRNGKey(0)))
    frame = np.random.default_rng(0).standard_normal(
        (1, 16, 16, 1)).astype(np.float32)
    ex = NullHopExecutor(cnn, policy)
    try:
        ex.run_frame(params, frame)  # compiles
        spans.clear()
        res = ex.run_frame(params, frame)
    finally:
        ex.close()
    (root,) = _by_name(spans.snapshot(), "repro.nullhop.frame")
    recs = [r for r in spans.snapshot() if r.frame == root.seq]
    ms = {name: sorted(_by_name(recs, f"repro.{name}"), key=lambda r: r.t0)
          for name in ("stream.input_tx", "stream.tx_wait", "stream.pack",
                       "stream.compute", "stream.rx_wait")}
    (inp,) = ms["stream.input_tx"]
    n = len(res.timing.layers)
    assert n == 5 and all(len(v) == n for k, v in ms.items()
                          if k != "stream.input_tx")

    def ns(r):
        return r.t1 - r.t0

    overlapped = policy.management.value == "interrupt"
    for i, lt in enumerate(res.timing.layers):
        wait, comp = ms["stream.tx_wait"][i], ms["stream.compute"][i]
        if overlapped:  # the window refill packed after the wait
            packs = [p for p in ms["stream.pack"]
                     if wait.t1 <= p.t0 < comp.t0]
        else:  # this layer's own pack before the wait
            packs = [ms["stream.pack"][i]]
        tx = (ns(wait) + sum(ns(p) for p in packs)) * 1e-9
        if i == 0:
            tx += ns(inp) * 1e-9
        assert lt.tx_s == pytest.approx(tx, rel=1e-12)
        assert lt.compute_s == pytest.approx(ns(comp) * 1e-9, rel=1e-12)
        assert lt.rx_s == pytest.approx(ns(ms["stream.rx_wait"][i]) * 1e-9,
                                        rel=1e-12)

    assert sum(r.nbytes for r in _by_name(recs, "repro.xfer.tx")) == sum(
        lt.tx_bytes for lt in res.timing.layers)
    assert sum(r.nbytes for r in _by_name(recs, "repro.xfer.rx")) == sum(
        lt.rx_bytes for lt in res.timing.layers)
    # the frame's direct children on its thread
    kids = [r for r in recs if r.parent == root.seq
            and r.thread == root.thread]
    assert sorted(r.name for r in kids) == [
        "repro.nullhop.fc", "repro.nullhop.oracle", "repro.nullhop.stream"]
    # the sparsity count ran on every layer's RX'd fmap
    counted = _by_name(recs, "repro.nullhop.oracle.layer")
    assert len(counted) == n
    assert sum(r.nbytes for r in counted) == sum(
        r.nbytes for r in _by_name(recs, "repro.xfer.rx"))
