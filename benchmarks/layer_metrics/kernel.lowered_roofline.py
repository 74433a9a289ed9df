from trace_reduce import roofline_share as read  # noqa: F401
