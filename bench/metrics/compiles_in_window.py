"""Executables JAX built inside the window."""
from bench.readers import compiles_in_window as compute  # noqa: F401
