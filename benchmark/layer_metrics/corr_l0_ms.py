"""Device time by stage() of the model: stages.stage_ms."""

from stages import stage_ms as read  # noqa: F401
