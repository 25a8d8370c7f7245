from repro.optim import adamw

__all__ = ["adamw"]
