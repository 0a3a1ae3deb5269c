"""Mean seconds per diagram of the host unpack of the fused kernel's
words into rows (``host_rows``): the program's ``stage.gradient.unpack``
spans.  Only the fused Pallas path unpacks, so other paths read None."""

from bench import program_spans


def read(run):
    return program_spans.seconds_per_diagram(run, "stage.gradient.unpack")
