"""Queries completed in the window over the window's seconds (host
clock)."""
from readers import completed_per_s as read  # noqa: F401
