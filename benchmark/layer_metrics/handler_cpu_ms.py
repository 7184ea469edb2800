"""CPU ms of a handler thread per answered request: stage_cpu.ms."""

from stage_cpu import ms as read  # noqa: F401
