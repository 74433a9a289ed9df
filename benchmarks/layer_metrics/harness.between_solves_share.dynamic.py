"""The share of the window that lies in none of the runner's solve spans:
what the benchmark itself costs between one solve's end and the next one's
start, its garbage collection ("collect") and its look at the result tiles'
host copies ("read_back") included."""

HARNESS_SPANS = ("collect", "read_back")


def read(run: dict) -> float | None:
    win = run["window"]
    if not win.spans:
        return None
    solving = sum(v for k, v in win.spans.items() if k not in HARNESS_SPANS)
    return 100.0 * (win.wall_s - solving) / win.wall_s
