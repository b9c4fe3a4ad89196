"""``queries_per_s`` of the reuse cell, under a name of its own so that
its bound follows its own spread."""
from readers import completed_per_s as read  # noqa: F401
