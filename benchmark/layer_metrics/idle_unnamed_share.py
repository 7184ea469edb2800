"""Device-idle time by the program's host stage: stages.idle_ms."""

from stages import idle_ms as read  # noqa: F401
