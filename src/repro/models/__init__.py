from repro.models.api import LM, make_demo_batch

__all__ = ["LM", "make_demo_batch"]
