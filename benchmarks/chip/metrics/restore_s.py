"""Mean ``dhp.restart`` (read the job record, restore the CMI onto the
device) over the window's resumes. Moves ``train_goodput_tok_s``."""

from benchmarks.chip.program_spans import mean_duration, window


def read(run):
    return mean_duration(window(run), "dhp.restart")
