"""Mean over the window's publishes of the time the publishing thread waited
for the chunks' hashes (``ckpt.hash_wait`` inside ``dhp.publish``).
Moves ``train_goodput_tok_s``."""

from benchmarks.chip.program_spans import per_publish


def read(run):
    return per_publish(run, {"ckpt.hash_wait"})
