"""What of a solve still has no owner: the runner's walls around the
program's entry points (add_taskpool, wait, sync, flush, fini) less every
span's self time in the program's phase table; ``ctx.init`` is left out on
both sides (the runner builds the Context under ``build_pool``, with the
collections)."""

from phases import self_seconds

RUNNER_SPANS = ("add_taskpool", "wait", "sync", "flush", "fini")


def read(run: dict) -> float | None:
    table = self_seconds()
    spans = run["window"].spans
    if not table or not all(k in spans for k in RUNNER_SPANS):
        return None
    owned = sum(v for k, v in table.items() if k != "ctx.init")
    return 100.0 * (1.0 - owned / sum(spans[k] for k in RUNNER_SPANS))
