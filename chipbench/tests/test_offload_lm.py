"""The expert-offload decode cell, whole, on the CPU at a tiny size: a
traced run comes out correct and reads every metric the cell adds; the
control in the program's place, or a step that leaves out a routed
expert, comes out not correct; the roofline reader on a recorded-shape
trace."""

import json
import sys

import pytest

from chipbench.harness import core
from chipbench.harness.peaks import PEAKS
from chipbench.tests.conftest import ROOT, copy_checkout

sys.path.insert(0, str(ROOT / "src"))

CELL = "tiny-moonlight.tinyoffload"
NEW = ["expert_tx_GBps", "expert_wait_ms", "route_rx_ms",
       "experts_tx_per_layer", "offload_step_roofline_pct",
       "offload_step_mfu_pct"]
SEED = 3_000_000_041


@pytest.fixture(scope="module")
def offload_root(tmp_path_factory):
    """A checkout with the tiny offload cell added by files and entries."""
    root = copy_checkout(tmp_path_factory.mktemp("offload"))
    cb = root / "chipbench"
    cfg = json.loads((cb / "configs" / "moonlight-16b-a3b.json").read_text())
    cfg.update(name="tiny-moonlight", num_hidden_layers=3, hidden_size=64,
               intermediate_size=96, num_attention_heads=4,
               num_key_value_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
               qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=8,
               num_experts_per_tok=2, moe_intermediate_size=32,
               vocab_size=512,
               # the CPU backend has no bf16 x bf16 -> f32 product
               torch_dtype="float32",
               serve={"slots": 2, "max_seq": 2048, "prefill_pad": 64},
               # tiny f32 runs read <= 1e-5 / 0 / 0; the float8 control
               # reads >= 0.1 on its logits
               check={"logit_rel_err": 1e-3, "route_flip_share": 0.01,
                      "route_tie_gap": 1e-4})
    (cb / "configs" / "tiny-moonlight.json").write_text(json.dumps(cfg))
    tr = json.loads((cb / "traffic" / "offload_decode.json").read_text())
    tr.update(sessions=2, warmup_steps=2, trace_seconds=1,
              prompt_tokens={"dist": "lognormal", "median": 24, "sigma": 0.5,
                             "min": 8, "max": 64, "round_to": 8})
    (cb / "traffic" / "tinyoffload.json").write_text(json.dumps(tr))
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": "tiny-moonlight", "source": "test",
                           "reduced": [], "why": "test",
                           "file": "chipbench/configs/tiny-moonlight.json"})
    doc["workloads"].append({"name": CELL, "config": "tiny-moonlight",
                             "traffic": "tinyoffload", "chips": 1,
                             "why": "test"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "moonlight-16b-a3b.offload_decode" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return root


def _run(root, capsys, trace=0, control=0):
    rc = core.main(["--workload", CELL, "--seed", str(SEED), "--seconds",
                    "3", "--trace", str(trace), "--control", str(control)],
                   root=root, require_chip=False)
    out = capsys.readouterr()
    assert rc == 0, out.err[-3000:]
    return json.loads(out.out.strip().splitlines()[-1])


def test_cell_resolves_with_its_metrics():
    from chipbench.harness.spec import Benchmark

    b = Benchmark(ROOT)
    assert b.problems() == []
    c = b.cell("moonlight-16b-a3b.offload_decode")
    assert {m.name for m in c.end_to_end} == {"setup_s", "frames_per_s",
                                              "frame_p95_ms"}
    assert {m.name for m in c.per_layer} == set(NEW)
    for cell in ("roshambo.ring4", "roshambo.polling"):
        assert not {m.name for m in b.cell(cell).per_layer} & set(NEW)


def test_traced_run_is_correct_and_reads_every_new_metric(
        offload_root, capsys, monkeypatch):
    # on the CPU no peaks are known; give the run the v5e's so that the
    # readers that divide by a peak are exercised (no number is reported)
    run = core.Run
    monkeypatch.setattr(core, "Run", lambda **kw: run(
        **dict(kw, peaks=kw["peaks"] or PEAKS["TPU v5 lite"])))
    r = _run(offload_root, capsys, trace=1)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    got = r["metrics"]
    # the CPU trace has no device plane: the roofline reads nothing here
    assert set(NEW) - {"offload_step_roofline_pct"} <= set(got), sorted(got)
    assert all(got[m]["value"] > 0 for m in got)
    assert got["experts_tx_per_layer"]["value"] <= 4  # 2 rows x top 2
    assert r["checks"]["route_flip_share"]["value"] == 0.0


def test_untraced_run_reports_the_end_to_end_metrics(offload_root, capsys):
    r = _run(offload_root, capsys)
    assert r["correct"]
    assert set(r["metrics"]) == {"frames_per_s", "frame_p95_ms", "setup_s"}


def test_control_in_the_programs_place_is_not_correct(offload_root, capsys):
    r = _run(offload_root, capsys, control=1)
    assert not r["correct"]
    assert r["checks"]["logit_rel_err"]["value"] > \
        r["checks"]["logit_rel_err"]["limit"]


def test_a_dropped_routed_expert_is_not_correct(offload_root, capsys,
                                                monkeypatch):
    from repro.models.moonlight import ExpertOffload

    experts = ExpertOffload._experts

    def dropping(self, m, lp, x, h, ids, w, chosen):
        last = chosen.max()
        return experts(self, m, lp, x, h, ids, w, chosen[chosen != last])

    monkeypatch.setattr(ExpertOffload, "_experts", dropping)
    assert not _run(offload_root, capsys)["correct"]


def test_roofline_reader_on_a_known_trace():
    from chipbench.harness import moonlight_counts
    from chipbench.harness.core import Run
    from chipbench.harness.spec import Benchmark, load_module

    cfg = json.loads((ROOT / "chipbench" / "configs" /
                      "moonlight-16b-a3b.json").read_text())
    lm = moonlight_counts.MoonlightLM.from_config(cfg)
    steps = [(0.0, 0.5, [1000, 1200, 900, 1500], [20] * 8),
             (0.5, 0.5, [1001, 1201, 901, 1501], [22] * 8)]
    trace = {"window": [0, 2_000_000_000],
             "spans": [["bench.step", 100, 900_000_000],
                       ["bench.step", 1_000_000_000, 900_000_000]],
             "modules": {"/device:TPU:0": [
                 ["jit_moonlight_route", 1_000, 30_000_000],
                 ["jit_moonlight_expert", 40_000_000, 50_000_000],
                 ["jit_other", 100_000_000, 500_000_000],
                 ["jit_moonlight_expert", 1_000_001_000, 70_000_000]]}}
    peaks = PEAKS["TPU v5 lite"]
    run = Run(cell=None, seconds=2.0, setup_s=1.0, trace=trace, peaks=peaks,
              data={"offload_steps": steps, "config": cfg})
    read = load_module(Benchmark(ROOT).metric_file(
        "offload_step_roofline_pct"), "roofline_under_test").read
    floor = sum(max(f / peaks["bf16_flops_per_s"],
                    b / peaks["hbm_bytes_per_s"])
                for f, b in (lm.decode_step(c, e) for _t, _w, c, e in steps))
    assert read(run) == pytest.approx(100.0 * floor / 0.15)
    # one expert of 17.3 MB is 3 x 2048 x 1408 bf16 numbers
    assert lm.expert_params * lm.itemsize == 3 * 2048 * 1408 * 2
