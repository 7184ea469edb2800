"""Slot traffic against the bandwidth: stream_metrics.slot_io_roofline."""

from stream_metrics import slot_io_roofline as read  # noqa: F401
