"""Share of the stream batches the host had placed in time
(batch_staged_ahead_share's reader, in a cell whose device batches are
groups of stream advances)."""

from layer_metrics.batch_staged_ahead_share import read  # noqa: F401
