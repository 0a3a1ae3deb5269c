"""Mean seconds per diagram of the gradient stage's copy of the kernel's
words (or rows) to host memory: the program's ``stage.gradient.d2h``
spans."""

from bench import program_spans


def read(run):
    return program_spans.seconds_per_diagram(run, "stage.gradient.d2h")
