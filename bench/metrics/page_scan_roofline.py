"""% of the HBM roofline the page-scan kernel reaches."""
from bench.readers import page_scan_roofline as compute  # noqa: F401
