"""The whole step's share of the chip's bf16 peak (%) in a cell that mostly decodes
(``Run.mfu``)."""


def read(run):
    return run.mfu()
