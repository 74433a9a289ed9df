"""XLA programs launched on the device per solve, counted on the trace's
"XLA Modules" line: 1 while the lowering emits one program for the pool."""


def read(run: dict) -> float | None:
    tr = run["trace"]
    if not tr or not run["peaks"] or not tr["launches"]:
        return None
    return tr["launches"] / run["window"].solves
