"""% of the traced span with no operation on the chip."""
from bench.readers import device_idle_share as compute  # noqa: F401
