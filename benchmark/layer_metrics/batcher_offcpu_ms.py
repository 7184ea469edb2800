"""Batcher-thread ms per device batch inside its own stages and not running: stage_cpu.ms."""

from stage_cpu import ms as read  # noqa: F401
