"""Tasks the device module ran per XLA call it made (64 when every fused
batch is full, 1 when nothing batches)."""


def read(run: dict) -> float | None:
    c = run["window"].counters
    if not c["xla_calls"]:
        return None
    return c["executed_tasks"] / c["xla_calls"]
