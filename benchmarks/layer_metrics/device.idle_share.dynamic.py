from trace_reduce import idle_share as read  # noqa: F401
