"""Median length of a whole `DeepModelTransformer.transform` of the cell's
table, the fetched columns back on the host (host clock)."""
from harness.readers import median_call_seconds as read  # noqa: F401
