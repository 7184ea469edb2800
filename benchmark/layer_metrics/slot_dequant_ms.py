"""Device ms a batched advance dequantising: pool_metrics.scope_ms."""

from pool_metrics import scope_ms as read  # noqa: F401
