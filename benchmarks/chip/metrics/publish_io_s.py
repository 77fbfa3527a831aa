"""Mean over the window's publishes of their storage time: waiting on the
object writers and their fsyncs (``ckpt.write_wait``), the manifest's commit
(``ckpt.commit``) and the job record with its GC (``jobstore.record``).
Moves ``train_goodput_tok_s``."""

from benchmarks.chip.program_spans import per_publish


def read(run):
    return per_publish(run, {"ckpt.write_wait", "ckpt.commit", "jobstore.record"})
