"""95th percentile of the latency of every query completed in the
window, from submit until its answer is ready and counted (host clock),
in seconds."""
from readers import latency_p95_s as read  # noqa: F401
