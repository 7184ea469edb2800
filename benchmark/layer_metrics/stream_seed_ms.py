"""Wall ms a device batch in raft.stream.seed (stage_ms_per_batch)."""

from stream_metrics import stage_ms_per_batch as read  # noqa: F401
