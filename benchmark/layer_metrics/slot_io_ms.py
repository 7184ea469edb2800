"""Device ms per batch moving slot rows: stream_metrics.slot_io_ms."""

from stream_metrics import slot_io_ms as read  # noqa: F401
