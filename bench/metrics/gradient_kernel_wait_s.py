"""Mean seconds per diagram that the gradient stage waits for its
jitted rows program (the fused kernel on a TPU) after dispatching it:
the program's ``stage.gradient.kernel`` spans."""

from bench import program_spans


def read(run):
    return program_spans.seconds_per_diagram(run, "stage.gradient.kernel")
