"""Bytes brought from the device to the host per byte committed to shared
memory: the engine's ``d2h_bytes_total`` (bytes of every array a host copy
was started on, or that was read with none started) over
``bytes_committed_total``, differences over the window and the final
flush.  1.00 when each piece of the state crosses once; a writer that
prefetches one object and reads another reads 2.00.  A program without
the counter reports nothing."""

LAYER = "checkpoint"
UNIT = "ratio"
BETTER = "lower"
SOURCE = "program_counter"


def read(run):
    c = run["counters"]
    crossed = c.get("ckpt.d2h_bytes_total")
    committed = c.get("ckpt.bytes_committed_total")
    return crossed / committed if crossed is not None and committed else None
