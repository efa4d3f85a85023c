"""Utilities: array helpers, logging, debug checks, profiling."""

from . import compile_watchdog, helpers, profiling, torch_interop
from .compile_watchdog import CompileWatchdog, RecompileError
from .profiling import device_memory_stats, throughput

__all__ = ["CompileWatchdog", "RecompileError", "compile_watchdog",
           "device_memory_stats", "helpers", "profiling", "throughput",
           "torch_interop"]
