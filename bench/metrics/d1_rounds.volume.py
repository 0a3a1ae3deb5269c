"""D1 rounds per diagram: the program's ``stage.d1_round`` spans, one
per wavefront round, or per pivot step when few columns take the
sequential burst path."""

from bench import program_spans


def read(run):
    return program_spans.count_per_diagram(run, "stage.d1_round")
