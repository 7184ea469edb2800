"""Advances served from a resident slot: stream_metrics.warm_share."""

from stream_metrics import warm_share as read  # noqa: F401
