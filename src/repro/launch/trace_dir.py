"""`--trace-dir DIR` of the launchers: the job's program spans and a
profiler trace of it, side by side.

Inside `traced(DIR)` the program's tracer (`repro.obs`) is on and
`jax.profiler` records the job into DIR (TensorBoard's layout:
`DIR/plugins/profile/<run>/*.xplane.pb`), where the spans also appear on the
host plane, on the device ops' clock.  At exit the spans are written to
`DIR/spans.jsonl`, one `obs.Record` a line, and a last line
`{"dropped": n}` counts the records the tracer's cap left out.
"""
from __future__ import annotations

import contextlib
import json
import os
from typing import Iterator, Optional

import jax

from .. import obs


@contextlib.contextmanager
def traced(trace_dir: Optional[str]) -> Iterator[None]:
    if trace_dir is None:
        yield
        return
    os.makedirs(trace_dir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # Python calls would slow the host
    opts.host_tracer_level = 2
    obs.enable()
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        obs.disable()
        records, dropped = obs.drain()
        with open(os.path.join(trace_dir, "spans.jsonl"), "w") as f:
            for r in records:
                f.write(json.dumps(r._asdict()) + "\n")
            f.write(json.dumps({"dropped": dropped}) + "\n")
