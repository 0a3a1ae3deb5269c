"""Mean seconds per diagram of the scatter of packed rows into gradient
fields: the program's ``stage.gradient.scatter`` spans."""

from bench import program_spans


def read(run):
    return program_spans.seconds_per_diagram(run, "stage.gradient.scatter")
