"""Host ms a cold restart waits for the batch staged behind its group
(stream_cold_ms's reader over raft.stream.cold.wait)."""

from layer_metrics.stream_cold_ms import read  # noqa: F401
