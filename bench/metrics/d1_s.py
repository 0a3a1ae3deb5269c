"""Mean seconds per diagram of the program's ``d1`` stage in the
window, as its ``StageReport`` gives them."""


def read(run):
    return run.stage_mean("d1")
