"""paddle_tpu.jit — dynamic-to-static compilation.

Parity: python/paddle/jit/ (reference — @to_static api.py:171, AST
transformer pipeline dy2static/, partial_program run_program op
paddle/fluid/eager/to_static/run_program_op_node.h, jit.save/load +
TranslatedLayer translated_layer.py).

TPU-native design (SURVEY.md §7): the trace front-end is JAX itself — a
to_static function traces the Python callable once per input signature into
a jaxpr → StableHLO executable (the CINN/PIR lowering collapses into XLA).
The compiled region participates in the eager tape as ONE GradNode whose VJP
is the XLA-compiled backward (exactly the reference's run_program-op-as-
GradNode design, §3.4) — so eager and compiled code mix freely.
jit.save serializes the StableHLO executable + params; jit.load returns a
TranslatedLayer.
"""
from .api import to_static, StaticFunction, not_to_static, ignore_module
from .save_load import save, load, TranslatedLayer
from .api import enable_to_static
from .convert_ops import bounded_loops

__all__ = ["to_static", "StaticFunction", "save", "load", "TranslatedLayer",
           "bounded_loops",
           "not_to_static", "enable_to_static"]


# -- translator logging knobs (parity: paddle/jit/dy2static/logging_utils
# set_code_level/set_verbosity).  The SOT/AST translator honors these via
# paddle_tpu.jit.sot logging.
_TRANSLATOR_LOG = {"code_level": -1, "verbosity": 0}


def set_code_level(level=100, also_to_stdout=False):
    """Parity: paddle.jit.set_code_level — log the transformed code at
    ``level`` (our translator logs captured StatementIR instead of AST
    stages)."""
    _TRANSLATOR_LOG["code_level"] = int(level)
    _TRANSLATOR_LOG["also_to_stdout"] = bool(also_to_stdout)


def set_verbosity(level=0, also_to_stdout=False):
    """Parity: paddle.jit.set_verbosity."""
    _TRANSLATOR_LOG["verbosity"] = int(level)
    _TRANSLATOR_LOG["also_to_stdout"] = bool(also_to_stdout)


__all__ += ["set_code_level", "set_verbosity",
            "LlamaLayerwiseTrainStep"]


def __getattr__(name):
    # lazy: layerwise pulls the llama model + pallas kernels, which
    # plain to_static/save/load users should not pay for at import
    if name == "LlamaLayerwiseTrainStep":
        from .layerwise import LlamaLayerwiseTrainStep
        return LlamaLayerwiseTrainStep
    raise AttributeError(name)
