"""Mean seconds per diagram of the dense edge comparison keys built in
critical extraction: the program's ``stage.extract_sort.edge_keys``
spans."""

from bench import program_spans


def read(run):
    return program_spans.seconds_per_diagram(run,
                                             "stage.extract_sort.edge_keys")
