"""Small shared utilities."""

from r2d2_tpu.utils.platform import enable_compile_cache, pin_platform

__all__ = ["enable_compile_cache", "pin_platform"]
