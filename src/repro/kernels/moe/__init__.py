from .ops import moe

__all__ = ["moe"]
