"""CPU ms of the batcher thread per device batch: stage_cpu.ms."""

from stage_cpu import ms as read  # noqa: F401
