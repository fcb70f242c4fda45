"""The reduction from a trace to busy time, idle share, program time and
idle gaps, on an extract of a trace recorded on a TPU v5e (two calls of a
2048x2048 bf16 matmul-and-sum and one of an elementwise tanh, 5 ms sleeps
between them) and on intervals worked by hand."""

import json
import pathlib

import pytest

from chipbench.harness import trace

DATA = pathlib.Path(__file__).resolve().parent / "data" / "trace_v5e.json"


@pytest.fixture
def recorded():
    return json.loads(DATA.read_text())


def test_recorded_busy_union_and_idle(recorded):
    # five ops, two of them (copy-start, copy-done) inside the matmul
    # program's span of 101750 ns: 25060 + (709389060 + 90087 - 709377400)
    # + 25296 = 152103 ns, less 3 ns between copy-start's end and copy-done
    assert trace.busy_ns(recorded, "/device:TPU:0") == pytest.approx(152100)
    assert trace.window_ns(recorded) == 13466109
    assert trace.idle_share(recorded) == pytest.approx(1 - 152100 / 13466109)


def test_recorded_program_time(recorded):
    evs = trace.module_events(recorded, ["jit__lambda"])
    assert [e[2] for e in evs] == [25062.0, 101750.0, 25298.0]
    assert trace.module_events(recorded, ["jit_apply_fn"]) == []


def test_recorded_breakdown_names_ops_and_gaps(recorded):
    b = trace.breakdown(recorded)
    ops = dict(b["device_ops"])
    assert ops["jit__lambda/convolution_reduce_fusion"] == pytest.approx(
        90087e-9)
    assert ops["jit__lambda/tanh_multiply_fusion"] == pytest.approx(
        (25060 + 25296) * 1e-9)
    gaps = b["idle_gaps"]
    assert len(gaps) <= 10
    assert gaps[0][0] == "bench.sleep"
    assert gaps == sorted(gaps, key=lambda g: -g[1])


def _synthetic():
    return {"ops": {"/device:TPU:0": [["a", "jit_x", 0, 10],
                                      ["b", "jit_x", 5, 10],
                                      ["c", "jit_y", 20, 10]]},
            "modules": {"/device:TPU:0": [["jit_x", 0, 15],
                                          ["jit_y", 20, 10]]},
            "spans": [["bench.window", 0, 40], ["bench.step", 0, 16],
                      ["bench.sleep", 16, 4], ["bench.step", 20, 12]],
            "window": [0, 40]}


def test_union_by_hand():
    d = _synthetic()
    assert trace.union([(0, 10), (5, 15), (20, 30)], 0, 40) == [
        (0, 15), (20, 30)]
    assert trace.busy_ns(d, "/device:TPU:0") == 25
    assert trace.idle_share(d) == pytest.approx(15 / 40)
    # [30, 40) overlaps the second step by 2 ns and nothing else;
    # [15, 20) overlaps the first step by 1 ns and the sleep by 4
    assert trace.idle_gaps(d) == [["bench.step", 10e-9],
                                  ["bench.sleep", 5e-9]]
    assert trace.per_span_module_ns(d, "bench.step", ["jit_x"]) == [15, 0]
    assert trace.per_span_module_ns(d, "bench.step", ["jit_y"]) == [0, 10]


def test_clock_offset_from_markers():
    d = _synthetic()
    # device clock 7 ns behind: marker programs at 100 and 300 on the host
    d["spans"] += [["bench.sync", 100, 6], ["bench.sync", 300, 6]]
    d["modules"]["/device:TPU:0"] += [["jit_chipbench_sync", 95, 2],
                                      ["jit_chipbench_sync", 295, 2]]
    off = trace.clock_offset(d)
    assert 5 - 0 <= off <= 5 + 4 and off == pytest.approx(7)
    moved = trace.align(d, off)
    assert moved["ops"]["/device:TPU:0"][0][2] == pytest.approx(7)


def test_loop_ops_count_only_their_own_time():
    ops = [["while", "jit_d", 0, 100], ["body.1", "jit_d", 10, 30],
           ["body.2", "jit_d", 50, 40], ["after", "jit_d", 100, 5]]
    assert trace.self_times(ops) == [30.0, 30.0, 40.0, 5.0]
    d = {"ops": {"/device:TPU:0": ops}, "modules": {}, "spans": [],
         "window": [0, 200]}
    top = dict(trace.top_ops(d))
    assert top["jit_d/body.2"] == pytest.approx(40e-9)
    assert top["jit_d/while"] == pytest.approx(30e-9)
