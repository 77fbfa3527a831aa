"""Mean over the window's publishes of their device-to-host copies
(``ckpt.d2h`` spans inside ``dhp.publish``). Moves ``train_goodput_tok_s``."""

from benchmarks.chip.program_spans import per_publish


def read(run):
    return per_publish(run, {"ckpt.d2h"})
