"""The program's spans read over the traced window: a traced tiny frame
run reports every metric read from them, and the pairing of program
frames with the trace's ``bench.run_frame`` spans recovers the clock
shift, or gives nothing where the two do not pair."""

import json
import sys

import pytest

from chipbench.harness import program_spans
from chipbench.harness.core import main
from chipbench.tests.conftest import ROOT

sys.path.insert(0, str(ROOT / "src"))
from repro.utils.spans import Record  # noqa: E402

SPAN_METRICS = {"nullhop_oracle_ms", "nullhop_self_ms", "stream_pack_ms",
                "runtime_queue_p95_ms.layer", "h2d_GBps", "d2h_GBps",
                "stream_self_ms"}


def test_traced_frame_run_reports_the_span_metrics(tiny_root, capsys):
    rc = main(["--workload", "tiny-cnn.tinyring", "--seed", "3000000023",
               "--seconds", "3", "--trace", "1"],
              root=tiny_root, require_chip=False)
    out = capsys.readouterr()
    assert rc == 0, out.err[-3000:]
    r = json.loads(out.out.strip().splitlines()[-1])
    assert r["correct"]
    got = r["metrics"]
    assert SPAN_METRICS <= set(got), sorted(got)
    assert all(got[m]["value"] > 0 for m in SPAN_METRICS)
    # the oracle and the frame's own time are parts of the host time
    # measured from outside
    assert (got["nullhop_oracle_ms"]["value"]
            + got["nullhop_self_ms"]["value"]
            < got["nullhop_host_ms"]["value"])


def test_gaps_names_idle_time_by_program_spans(tiny_root):
    from chipbench.tools import gaps

    run = gaps.traced_run(tiny_root, "tiny-cnn.tinyring", 3000000029, 3,
                          require_chip=False)
    rep = gaps.report(run, n_gaps=5)
    assert rep["frames"] > 0 and rep["idle_ms"] > 0
    assert 0.0 <= rep["idle_uncovered_share"] <= 1.0
    assert 0 < len(rep["gaps"]) <= 5
    assert all(g["span"].startswith(("repro.", "host:")) for g in rep["gaps"])
    assert all(0.0 <= g["covered"] <= 1.0 for g in rep["gaps"])
    # the frame is its own time plus its direct children on its thread
    per = {name: v["ms"] for name, v in rep["per_frame"].items()}
    parts = (rep["self_ms"]["repro.nullhop.frame"]
             + per["repro.nullhop.oracle"] + per["repro.nullhop.fc"]
             + per["repro.nullhop.stream"])
    assert parts == pytest.approx(per["repro.nullhop.frame"], rel=1e-9)
    assert 0 < rep["self_ms"]["repro.nullhop.stream"] < per[
        "repro.nullhop.stream"]


SHIFT = 7_000_123_456  # trace clock minus program clock, ns


def _frames(n, *, t_first=1_000_000, period=30_000_000):
    """n frames of one main-thread child each, and one worker record,
    after one record from before the frames."""
    recs = [Record("repro.xfer.tx", 0, 500, "w", 1, None, None, 64)]
    seq = 2
    for i in range(n):
        t0 = t_first + i * period
        root = seq
        recs.append(Record("repro.nullhop.oracle", t0 + 100, t0 + 600,
                           "main", seq + 1, root, root, 0))
        recs.append(Record("repro.xfer.tx", t0 + 50, t0 + 250, "w",
                           seq + 2, seq + 1, root, 1000))
        recs.append(Record("repro.nullhop.frame", t0, t0 + 1000, "main",
                           root, None, root, 0))
        seq += 3
    return recs


def _starts(recs):
    return [r.t0 + SHIFT for r in recs if r.name == "repro.nullhop.frame"]


def test_pairing_recovers_a_known_shift():
    recs = _frames(10)
    starts = [s + j for s, j in zip(_starts(recs)[-6:], [-3, 5, 0, 2, 1, 0])]
    w = program_spans.pair(starts, recs)
    assert w is not None and w.shift_ns == pytest.approx(SHIFT, abs=5)
    assert [f.seq for f in w.frames] == [
        r.seq for r in recs if r.name == "repro.nullhop.frame"][-6:]
    assert len(w.records) == 6 * 3
    assert w.per_frame_ms("repro.nullhop.oracle") == pytest.approx(500e-6)
    assert program_spans.self_ns(w.frames[0], w.records) == 500
    assert program_spans.gbps(w.named("repro.xfer.tx")) == pytest.approx(5.0)


@pytest.mark.parametrize("case", ["count", "spread", "overflow"])
def test_pairing_fails_cleanly(case):
    recs = _frames(4)
    starts = _starts(recs)
    if case == "count":  # more bench frames than program frames
        starts = [starts[0] - 30_000_000] + starts
    elif case == "spread":  # one frame 1 ms off its bench span
        starts[2] += program_spans.MAX_SPREAD_NS
    else:  # the ring dropped the first frame's earlier records
        recs = recs[2:]
    assert program_spans.pair(starts, recs) is None


def test_no_window_without_a_trace():
    class Run:
        trace = None
        data: dict = {}
    assert program_spans.window(Run()) is None
