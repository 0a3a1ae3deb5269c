"""Mean seconds per diagram of the program's ``extract_sort`` stage in the
window, as its ``StageReport`` gives them."""


def read(run):
    return run.stage_mean("extract_sort")
