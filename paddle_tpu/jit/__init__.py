"""paddle.jit parity: trace-to-XLA compilation (SURVEY.md §2.8 dy2static row).

No AST rewriting: `to_static` traces ordinary Python forward into one XLA
program; `TrainStep` fuses forward+backward+update; `functional_call` is the
Layer->pure-function bridge everything (including pjit sharding) builds on.
"""
from .api import (InputSpec, StaticFunction, TranslatedLayer, ignore_module,
                  load, not_to_static, save, to_static, enable_to_static,
                  set_verbosity, set_code_level)
from .functional import functional_call, load_state, raw_state
from .training import StepProgram, TrainStep, last_step_program

__all__ = ["to_static", "not_to_static", "ignore_module", "InputSpec",
           "StaticFunction", "save", "load", "TranslatedLayer",
           "functional_call", "raw_state", "load_state", "TrainStep",
           "StepProgram", "last_step_program",
           "enable_to_static", "set_verbosity", "set_code_level"]
