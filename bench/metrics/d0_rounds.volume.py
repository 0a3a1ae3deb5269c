"""D0 pointer-jumping rounds per diagram: the program's
``stage.d0_round`` spans inside its ``stage.d0`` stage (D_top runs the
same rounds on the dual graph; those are not counted)."""

from bench import program_spans


def read(run):
    return program_spans.count_per_diagram(run, "stage.d0_round",
                                           inside="stage.d0")
