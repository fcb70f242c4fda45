"""TransferRuntime.class_summary() TOKEN row, dispatch_p99_ms, read when
the window closes (the runtime's own window of recent samples)."""

from chipbench.harness import records


def read(run):
    row = records.runtime_row(run, "token")
    return row["dispatch_p99_ms"] if row and row.get("completed") else None
