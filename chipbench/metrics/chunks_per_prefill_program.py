"""Prefill chunks a prefill program: the window's mean of ``chunks``
(the chunks a tick ran: its admitting slots' and the first of each
request it admitted) over the mean of ``chunk_programs`` (the programs
they ran in), both the scheduler's own counts on ``serving.tick``. A
program reads every weight of the model once whatever it holds, so
this is how many chunks share one read. A scheduler whose ticks carry
no ``chunk_programs`` runs each chunk as a program of its own: 1.0.
Layer: server."""
from chipbench.metrics._program_spans import mean_tick_argument


def read(run):
    programs = mean_tick_argument(run, "chunk_programs")
    if programs is None:
        alone = mean_tick_argument(run, "admitting") is not None
        return 1.0 if alone else None
    chunks = mean_tick_argument(run, "chunks")
    if chunks is None or programs <= 0:
        return None
    return chunks / programs
