"""Worker runtime: seconds the program spent tracing functions to
jaxprs and lowering them to MLIR before the timed window opened --
JAX's ``jaxpr_trace_duration`` + ``jaxpr_to_mlir_module_duration``,
outermost calls only (a jitted function traced inside another's trace
is inside its caller's seconds and is not counted again)."""

from benchmark import setup_spans


def read(facts):
    return setup_spans.trace_lower_s(facts)
