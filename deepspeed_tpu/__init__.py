"""deepspeed_tpu — a TPU-native large-model training & inference framework.

Capability parity with DeepSpeed (reference: ``deepspeed/__init__.py``), designed
TPU-first: named device meshes + XLA collectives instead of NCCL process groups,
sharding specs instead of runtime partitioning hooks, jit-compiled train steps
instead of engine-orchestrated streams, Pallas kernels instead of CUDA.

Public entry points (reference parity):
- :func:`initialize` — config + model → (engine, optimizer, dataloader, scheduler)
  (reference ``deepspeed/__init__.py:80``)
- :func:`init_inference` — inference engine (reference :313)
- ``comm`` — collectives API (reference ``deepspeed/comm``)
"""

__version__ = "0.1.0"

# Resolved on first use (PEP 562), not at import: the launcher and the
# autotuning parent import this package and then start children that need the
# chip, so importing ``deepspeed_tpu`` alone must not import JAX.
_LAZY = {"comm": ("comm", None),
         "get_accelerator": ("accelerator", "get_accelerator"),
         "DeepSpeedTPUConfig": ("runtime.config", "DeepSpeedTPUConfig"),
         "parse_config": ("runtime.config", "parse_config")}


def __getattr__(name):
    import importlib

    if name not in _LAZY:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    module, attr = _LAZY[name]
    value = importlib.import_module(f"{__name__}.{module}")
    if attr is not None:
        value = getattr(value, attr)
    globals()[name] = value
    return value


def initialize(*args, **kwargs):
    from .runtime.engine import initialize as _init

    return _init(*args, **kwargs)


def init_inference(*args, **kwargs):
    from .inference.engine import init_inference as _init

    return _init(*args, **kwargs)


def tp_model_init(*args, **kwargs):
    from .runtime.zero_init import tp_model_init as _init

    return _init(*args, **kwargs)


class _ZeroNamespace:
    """``deepspeed_tpu.zero`` — reference ``deepspeed.zero`` namespace."""

    @property
    def Init(self):
        from .runtime.zero_init import Init

        return Init

    @property
    def GatheredParameters(self):
        from .runtime.zero_init import GatheredParameters

        return GatheredParameters

    @property
    def materialize_sharded(self):
        from .runtime.zero_init import materialize_sharded

        return materialize_sharded


zero = _ZeroNamespace()
