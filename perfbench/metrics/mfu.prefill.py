"""The whole step's share of the chip's bf16 peak (%) in a cell whose window prefills
(``Run.mfu``)."""


def read(run):
    return run.mfu()
