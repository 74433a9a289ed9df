"""Of the dependency edges ``release_deps`` handed to local successors, the
share that went through a resolved release plan (successor class, flow, input
dep and mask bit stated once per task class and out-dep) and not through the
per-edge walk that derives them again: the program's counters
``release_edges_planned / release_edges``, the process's totals over the
contexts that have ended (every solve of a dynamic cell is one), warm-up solves
included: they run the same graph.  100 on a one-rank PTG without counted
successors or typed edges.  A program without the counters reads as nothing."""


def read(run: dict) -> float | None:
    from parsec_tpu.runtime import scheduling
    totals = getattr(scheduling, "release_totals", None)
    if not totals or not totals["edges"]:
        return None
    return 100.0 * totals["planned"] / totals["edges"]
