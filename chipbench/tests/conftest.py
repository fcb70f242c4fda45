"""The benchmark's own tests, on the CPU. Run them by naming the path:

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests

``tiny_root`` is a checkout of its own with two configurations cut to a
size the CPU runs in seconds, added as files and entries only."""

import json
import pathlib
import shutil
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


# the serving metrics as the tiny serving cell reports them: those that
# BENCHMARK.json already holds gain the tiny cell in their workloads, the
# others come in as new entries
SERVING_METRICS = [
    ("end_to_end", {"name": "ttft_p75_ms", "unit": "ms", "better": "lower",
      "bound": 0.25, "source": "host_clock"}),
    ("end_to_end", {"name": "itl_p50_ms", "unit": "ms", "better": "lower",
      "bound": 0.01, "source": "host_clock"}),
    ("per_layer", {"name": "runtime_dispatch_p99_ms.token", "unit": "ms",
      "better": "lower", "source": "program_counter",
      "layer": "transfer runtime", "moves": "itl_p50_ms"}),
    ("per_layer", {"name": "decode_step_ms", "unit": "ms", "better": "lower",
      "source": "host_clock", "layer": "serving engine",
      "moves": "itl_p50_ms"}),
    ("per_layer", {"name": "admit_step_ms", "unit": "ms", "better": "lower",
      "source": "host_clock", "layer": "serving engine",
      "moves": "ttft_p75_ms"}),
    ("per_layer", {"name": "decode_roofline_pct", "unit": "%",
      "better": "higher", "source": "device_trace", "layer": "kernels",
      "moves": "itl_p50_ms"}),
    ("per_layer", {"name": "decode_mfu_pct", "unit": "%", "better": "higher",
      "source": "host_clock", "layer": "whole step", "moves": "itl_p50_ms"}),
    ("per_layer", {"name": "device_idle_pct.serve", "unit": "%",
      "better": "lower", "source": "device_trace", "layer": "device",
      "moves": "itl_p50_ms"}),
]


def add_tiny_cells(root: pathlib.Path) -> None:
    """Add tiny-lm.tinychat and tiny-cnn.tinyring under ``root`` by new
    files and new entries of its BENCHMARK.json."""
    cb = root / "chipbench"
    lm = json.loads((cb / "configs" / "h2o-danube-1.8b.json").read_text())
    lm.update(name="tiny-lm", num_hidden_layers=2, hidden_size=64,
              intermediate_size=128, num_attention_heads=4,
              num_key_value_heads=2, vocab_size=512, sliding_window=32,
              serve={"slots": 4, "max_seq": 256},
              # tiny runs read <= 0.023, the float8 control >= 0.31
              check={"max_logit_gap": 0.1})
    (cb / "configs" / "tiny-lm.json").write_text(json.dumps(lm))
    cnn = json.loads((cb / "configs" / "roshambo.json").read_text())
    # at 16x16, tiny runs read <= 5.4e-7, the three-pass control >= 7.7e-6
    cnn.update(name="tiny-cnn", input_hw=16, check={"logit_rel_err": 3e-6})
    cnn["frames"] = dict(cnn["frames"], pool=8, events_per_frame=200)
    (cb / "configs" / "tiny-cnn.json").write_text(json.dumps(cnn))
    (cb / "traffic" / "tinychat.json").write_text(json.dumps({
        "kind": "requests", "loop": "open",
        "arrivals": {"process": "poisson", "rate_per_s": 20.0},
        "prompt_tokens": {"dist": "lognormal", "median": 48, "sigma": 0.8,
                          "min": 16, "max": 128, "round_to": 32},
        "output_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.8,
                          "min": 2, "max": 24},
        "trace_seconds": 1,
        "check": {"min_tokens": 64, "min_requests": 2, "max_requests": 6}}))
    (cb / "traffic" / "tinyring.json").write_text(json.dumps({
        "kind": "frames", "loop": "closed", "in_flight": 1,
        "management": "kernel_level_ring", "ring_depth": 4,
        "warmup_frames": 2, "trace_seconds": 1}))
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["configs"] += [
        {"name": "tiny-lm", "source": "test", "reduced": [], "why": "test",
         "file": "chipbench/configs/tiny-lm.json"},
        {"name": "tiny-cnn", "source": "test", "reduced": [], "why": "test",
         "file": "chipbench/configs/tiny-cnn.json"}]
    doc["workloads"] += [
        {"name": "tiny-lm.tinychat", "config": "tiny-lm",
         "traffic": "tinychat", "chips": 1, "why": "test"},
        {"name": "tiny-cnn.tinyring", "config": "tiny-cnn",
         "traffic": "tinyring", "chips": 1, "why": "test"}]
    serving = {m["name"] for _kind, m in SERVING_METRICS}
    have = set()
    for m in doc["end_to_end"] + doc["per_layer"]:
        have.add(m["name"])
        wl = m.get("workloads")
        if wl and "roshambo.ring4" in wl:
            wl.append("tiny-cnn.tinyring")
        if wl and m["name"] in serving:
            wl.append("tiny-lm.tinychat")
    for kind, m in SERVING_METRICS:
        if m["name"] not in have:
            doc[kind].append(dict(m, workloads=["tiny-lm.tinychat"]))
    (root / "BENCHMARK.json").write_text(json.dumps(doc))


def copy_checkout(dst: pathlib.Path) -> pathlib.Path:
    """BENCHMARK.json and the benchmark's directory, with the program's
    source linked in."""
    dst.mkdir(parents=True, exist_ok=True)
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", dst / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (dst / "src").symlink_to(ROOT / "src")
    return dst


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory) -> pathlib.Path:
    root = copy_checkout(tmp_path_factory.mktemp("checkout"))
    add_tiny_cells(root)
    return root
