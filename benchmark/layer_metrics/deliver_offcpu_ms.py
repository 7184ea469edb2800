"""Off-CPU ms per device batch of batch.deliver: stage_cpu.ms."""

from stage_cpu import ms as read  # noqa: F401
