"""Benchmark runner — one function per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only transfer_sweep,...]

Prints ``name,us_per_call,derived`` CSV rows (plus the full dict per row on
stderr-like detail lines prefixed '#').
"""

from __future__ import annotations

import argparse
import sys
import time

from benchmarks import (
    adaptive_drift,
    collective_overlap,
    fault_recovery,
    multichannel_sweep,
    policy_ablation,
    qos_contention,
    roshambo_table,
    sg_vs_pack,
    streaming_layers,
    tenant_isolation,
    transfer_sweep,
    txrx_balance,
)
from repro.utils.compile_cache import enable_compile_cache

BENCHES = {
    "transfer_sweep": transfer_sweep.run,  # Fig 4 / Fig 5
    "roshambo_table": roshambo_table.run,  # Table I
    "policy_ablation": policy_ablation.run,  # single/double x unique/blocks
    "txrx_balance": txrx_balance.run,  # loop-back scenario
    "streaming_layers": streaming_layers.run,  # NullHop model at LM scale
    "multichannel_sweep": multichannel_sweep.run,  # striped rings + adaptive
    "sg_vs_pack": sg_vs_pack.run,  # scatter-gather vs staging-copy pack
    "adaptive_drift": adaptive_drift.run,  # online refit vs stale plan
    "qos_contention": qos_contention.run,  # shared-runtime QoS arbitration
    "tenant_isolation": tenant_isolation.run,  # tier-2 heavy-hitter WFQ
    "fault_recovery": fault_recovery.run,  # quarantine + replan vs stall
    "collective_overlap": collective_overlap.run,  # blocks-mode collectives
}


def _derived(row: dict) -> str:
    for k in ("tx_us_per_byte", "roundtrip_ms", "frame_ms",
              "collective_bytes_per_dev", "tx_gbps",
              "token_rx_p99_ms"):
        if k in row:
            return f"{k}={row[k]}"
    return ""


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    enable_compile_cache()
    names = [n.strip() for n in args.only.split(",") if n.strip()] or list(
        BENCHES)

    failures: list[str] = []
    print("name,us_per_call,derived")
    for name in names:
        fn = BENCHES[name]
        t0 = time.perf_counter()
        try:
            rows = fn()
        except Exception as e:  # noqa: BLE001
            print(f"{name},nan,error={type(e).__name__}")
            print(f"# {name} ERROR: {e}", file=sys.stderr)
            failures.append(name)
            continue
        us = (time.perf_counter() - t0) * 1e6 / max(len(rows), 1)
        for row in rows:
            print(f"# {row}")
        print(f"{name},{us:.1f},{_derived(rows[0]) if rows else ''}")
        try:
            if name == "streaming_layers":
                doc = streaming_layers.write_bench_json(rows)
                print(f"# wrote BENCH_transfer.json (ring/seed frames_per_s "
                      f"ratio {doc['frames_per_s_ratio_ring_over_seed']})")
            if name == "multichannel_sweep":
                doc = multichannel_sweep.merge_bench_json(rows)
                mc = doc["multichannel"]
                print(f"# merged multichannel rows into BENCH_transfer.json "
                      f"(single-ring/multi tx us/B ratio "
                      f"{mc['tx_us_per_byte_ratio_single_ring_over_multi']})")
            if name == "sg_vs_pack":
                doc = sg_vs_pack.merge_bench_json(rows)
                sc = doc["staging_copy"]
                print(f"# merged sg_vs_pack rows into BENCH_transfer.json "
                      f"(few-large pack/SG tx us/B ratio "
                      f"{sc['pack_over_sg_us_per_byte_few_large']}, "
                      f"decisions few-large={sc['decision_few_large']} "
                      f"many-small={sc['decision_many_small']})")
            if name == "adaptive_drift":
                doc = adaptive_drift.merge_bench_json(rows)
                ad = doc["adaptive_drift"]
                print(f"# merged adaptive_drift rows into BENCH_transfer.json "
                      f"(post-drift static/online recovery ratio "
                      f"{ad['recovery_ratio_static_over_online']})")
            if name == "qos_contention":
                doc = qos_contention.merge_bench_json(rows)
                qc = doc["qos_contention"]
                print(f"# merged qos_contention rows into BENCH_transfer.json "
                      f"(token-RX p99 per-engine/runtime ratio "
                      f"{qc['p99_ratio_per_engine_over_runtime']}, "
                      f"fifo/runtime "
                      f"{qc['p99_ratio_fifo_over_runtime']}, coalescing "
                      f"b32 {doc['coalescing']['speedup_b32']}x)")
            if name == "tenant_isolation":
                ti = tenant_isolation.merge_bench_json(rows)
                print(f"# merged tenant_isolation rows into "
                      f"BENCH_transfer.json (victim p99 vs noflood: wfq "
                      f"{ti['isolation_ratio_wfq']}x, single-tier "
                      f"{ti['isolation_ratio_single_tier']}x)")
        except Exception as e:  # noqa: BLE001 — a merge failure is a failure
            print(f"# {name} MERGE ERROR: {e}", file=sys.stderr)
            failures.append(name)
    if failures:
        # a sub-benchmark that died must fail the run (the CI smoke lane
        # gates on this exit code — silent skips made the lane vacuous)
        print(f"# FAILED benches: {','.join(failures)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
