"""A whole run on the CPU at a tiny size, the chip check skipped: sound,
it comes out correct; with the timed path broken underneath, once for each
fault the cell can have, it comes out not correct."""

import json

import numpy as np
import pytest

from chipbench.harness.core import main


def _run(root, workload, capsys, seed=3_000_000_019, trace=0, control=0):
    rc = main(["--workload", workload, "--seed", str(seed), "--seconds",
               "3", "--trace", str(trace), "--control", str(control)],
              root=root, require_chip=False)
    out = capsys.readouterr()
    assert rc == 0, out.err[-3000:]
    return json.loads(out.out.strip().splitlines()[-1])


def test_sound_lm_run_is_correct(tiny_root, capsys):
    r = _run(tiny_root, "tiny-lm.tinychat", capsys)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"ttft_p75_ms", "itl_p50_ms", "setup_s"}
    assert list(r)[-1] == "checks"


def test_sound_cnn_run_is_correct(tiny_root, capsys):
    r = _run(tiny_root, "tiny-cnn.tinyring", capsys)
    assert r["correct"] and r["attempted"] > 0
    assert set(r["metrics"]) == {"frames_per_s", "frame_p95_ms", "setup_s"}


def test_traced_run_reports_per_layer_metrics(tiny_root, capsys):
    r = _run(tiny_root, "tiny-lm.tinychat", capsys, trace=1)
    assert r["correct"]
    assert {"decode_step_ms", "admit_step_ms"} <= set(r["metrics"])
    assert r["device"]["window_s"] > 0 and "breakdown" in r


def test_token_altered_where_produced(tiny_root, capsys, monkeypatch):
    from repro.serve.continuous import ContinuousBatchingEngine as CBE

    step = CBE.step

    def altered(self):
        n = step(self)
        for r in self.slots:
            if r is not None and len(r.tokens) >= 2:
                r.tokens[-1] = (r.tokens[-1] + 1) % self.model.cfg.vocab
        return n

    monkeypatch.setattr(CBE, "step", altered)
    assert not _run(tiny_root, "tiny-lm.tinychat", capsys)["correct"]


def test_decode_returns_its_cache_unchanged(tiny_root, capsys, monkeypatch):
    from repro.serve.continuous import ContinuousBatchingEngine as CBE

    init = CBE.__init__

    def stale_init(self, *a, **k):
        init(self, *a, **k)
        decode = self._decode

        def stale(params, tokens, cache):
            logits, _new = decode(params, tokens, cache)
            return logits, cache

        self._decode = stale

    monkeypatch.setattr(CBE, "__init__", stale_init)
    assert not _run(tiny_root, "tiny-lm.tinychat", capsys)["correct"]


def test_frame_answer_altered_where_produced(tiny_root, capsys, monkeypatch):
    from repro.accel.nullhop import NullHopExecutor

    run_frame = NullHopExecutor.run_frame

    def altered(self, params, frame):
        res = run_frame(self, params, frame)
        res.logits = np.array(res.logits)
        res.logits[0, 0] += 1e-3 * np.abs(res.logits).max()
        return res

    monkeypatch.setattr(NullHopExecutor, "run_frame", altered)
    assert not _run(tiny_root, "tiny-cnn.tinyring", capsys)["correct"]


@pytest.mark.parametrize("workload", ["tiny-lm.tinychat",
                                      "tiny-cnn.tinyring"])
def test_control_in_the_programs_place_is_not_correct(tiny_root, capsys,
                                                      workload):
    """The reference one precision step lower, checked in the program's
    place by the run's own comparison, fails the configuration's limit."""
    r = _run(tiny_root, workload, capsys, control=1)
    cfg = json.loads((tiny_root / "chipbench" / "configs" /
                      f"{workload.split('.')[0]}.json").read_text())
    assert not r["correct"]
    for name, c in r["checks"].items():
        assert c["limit"] == cfg["check"][name]
        assert c["value"] > c["limit"]


def test_cnn_control_reads_above_the_program():
    import jax.numpy as jnp

    from chipbench.harness import reference, traffic, weights
    from chipbench.tests.conftest import ROOT

    cfg = json.loads((ROOT / "chipbench" / "configs" /
                      "roshambo.json").read_text())
    p = weights.cnn_weights(cfg, 11)
    x = jnp.asarray(traffic.dvs_frames(dict(cfg["frames"], pool=16), 64, 1,
                                       11)[:, 0])
    ref = reference.cnn_logits(cfg, p, x)
    low = reference.cnn_logits(cfg, p, x, control=True)
    assert reference.rel_err(low, ref) > cfg["check"]["logit_rel_err"]
