"""% of the window spent in the host page fetch (streamed cells)."""
from bench.readers import fetch_wall_share as compute  # noqa: F401
