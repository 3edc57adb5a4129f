"""repro — a push/pull-native graph + ML training/serving framework in JAX.

Reproduction of Besta et al., "To Push or To Pull: On Reducing
Communication and Synchronization in Graph Computations" (HPDC 17),
adapted to TPU/XLA semantics, plus the assigned architecture zoo.
"""

__version__ = "1.0.0"


def __getattr__(name):
    # `repro.api` without forcing the full algorithm import at package
    # import time (models/train/dist users never pay for it).
    if name == "api":
        import importlib
        return importlib.import_module(".api", __name__)
    raise AttributeError(f"module 'repro' has no attribute {name!r}")
