"""Page records read from the host memmap per answered query."""
from bench.readers import pages_fetched_per_query as compute  # noqa: F401
