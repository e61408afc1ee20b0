"""Of ``compile.step_first_call_s``, the seconds jax spent in Python:
tracing the step to a jaxpr (``compile.step_trace_s``) and lowering it to
a module (``compile.step_lower_s``), as ``jax.monitoring`` reported them
inside the call (nested traces counted once). Every process pays them,
whatever the compile cache holds: a kernel whose Python loop unrolls
shows here and nowhere else. None on a program without the counters."""
from lib import program_counters


def read(run: dict):
    parts = [program_counters.counter(name) for name in
             ("compile.step_trace_s", "compile.step_lower_s")]
    if any(p is None for p in parts):
        return None
    return sum(parts)
