"""Encoder passes per advance: stream_metrics.fnet_passes_per_pair."""

from stream_metrics import fnet_passes_per_pair as read  # noqa: F401
