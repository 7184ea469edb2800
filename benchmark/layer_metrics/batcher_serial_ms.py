"""Batcher-thread ms per device batch outside engine.wait: stages.serial_ms."""

from stages import serial_ms as read  # noqa: F401
