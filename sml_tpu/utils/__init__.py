from .profiler import PROFILER

__all__ = ["PROFILER"]
