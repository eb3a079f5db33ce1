"""repro.runtime — distribution: sharding rules, step functions, gradient
compression, pipeline parallelism."""
from . import sharding
from .steps import (abstract_batch, abstract_cache, abstract_state,
                    make_train_state, make_train_step_fn, model_axes,
                    prefill_step, serve_step, train_step)

__all__ = ["sharding", "abstract_batch", "abstract_cache", "abstract_state",
           "make_train_state", "make_train_step_fn", "model_axes",
           "prefill_step", "serve_step", "train_step"]
