"""Median length of a whole `recommend_for_all_users` call (host clock)."""
from harness.readers import median_call_seconds as read  # noqa: F401
