"""Page records read per answered query."""
from bench.readers import pages_per_query as compute  # noqa: F401
