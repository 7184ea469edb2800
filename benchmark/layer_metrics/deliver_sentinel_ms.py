"""Wall ms per device batch of deliver's non-finite pass: stages.serial_ms."""

from stages import serial_ms as read  # noqa: F401
